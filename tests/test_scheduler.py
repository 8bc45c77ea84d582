"""Scheduler pipeline tests: budget gating, overlap, failure propagation."""

import asyncio
import os

import pytest

from tpusnap.io_types import (
    BufferConsumer,
    BufferStager,
    ReadReq,
    WriteReq,
)
from tpusnap.knobs import override_memory_budget_bytes
from tpusnap.scheduler import (
    PendingIOWork,
    execute_read_reqs,
    execute_write_reqs,
    get_process_memory_budget_bytes,
    sync_execute_write_reqs,
)
from tpusnap.storage_plugins.fs import FSStoragePlugin


class TrackingStager(BufferStager):
    """Stager that tracks global concurrent staging cost."""

    live_cost = 0
    peak_cost = 0

    def __init__(self, data: bytes, cost: int):
        self.data = data
        self.cost = cost

    async def stage_buffer(self, executor=None):
        TrackingStager.live_cost += self.cost
        TrackingStager.peak_cost = max(
            TrackingStager.peak_cost, TrackingStager.live_cost
        )
        await asyncio.sleep(0.01)
        # buffer stays "live" until the write completes; we approximate by
        # decrementing at write time via WriteTracker below
        return self.data

    def get_staging_cost_bytes(self) -> int:
        return self.cost


class ByteConsumer(BufferConsumer):
    def __init__(self, sink: dict, key: str, cost: int = 0):
        self.sink = sink
        self.key = key
        self.cost = cost

    async def consume_buffer(self, buf, executor=None) -> None:
        self.sink[self.key] = bytes(buf)

    def get_consuming_cost_bytes(self) -> int:
        return self.cost


class FaultyStager(BufferStager):
    async def stage_buffer(self, executor=None):
        raise RuntimeError("staging boom")

    def get_staging_cost_bytes(self) -> int:
        return 10


class FaultyPlugin(FSStoragePlugin):
    async def write(self, write_io) -> None:
        raise OSError("storage boom")


def test_write_then_read_roundtrip(tmp_path):
    plugin = FSStoragePlugin(root=str(tmp_path))
    blobs = {f"blob{i}": os.urandom(1000 + i) for i in range(40)}
    write_reqs = [
        WriteReq(path=k, buffer_stager=TrackingStager(v, cost=len(v)))
        for k, v in blobs.items()
    ]
    loop = asyncio.new_event_loop()
    try:
        pending = sync_execute_write_reqs(
            write_reqs, plugin, memory_budget_bytes=1 << 30, rank=0, event_loop=loop
        )
        assert isinstance(pending, PendingIOWork)
        pending.sync_complete(loop)

        sink = {}
        read_reqs = [
            ReadReq(path=k, buffer_consumer=ByteConsumer(sink, k, cost=len(v)))
            for k, v in blobs.items()
        ]
        loop.run_until_complete(
            execute_read_reqs(read_reqs, plugin, 1 << 30, rank=0)
        )
        assert sink == blobs
    finally:
        loop.close()


def test_budget_gates_staging(tmp_path):
    """With a budget of 2 units and 8 one-unit items, peak concurrent
    staging cost must never exceed the budget."""
    TrackingStager.live_cost = 0
    TrackingStager.peak_cost = 0
    plugin = FSStoragePlugin(root=str(tmp_path))

    unit = 1000
    blobs = {f"b{i}": os.urandom(unit) for i in range(8)}

    class DecrementingPlugin(FSStoragePlugin):
        async def write(self, write_io) -> None:
            await super().write(write_io)
            TrackingStager.live_cost -= len(write_io.buf)

    plugin = DecrementingPlugin(root=str(tmp_path))
    write_reqs = [
        WriteReq(path=k, buffer_stager=TrackingStager(v, cost=unit))
        for k, v in blobs.items()
    ]

    async def go():
        pending = await execute_write_reqs(
            write_reqs, plugin, memory_budget_bytes=2 * unit, rank=0
        )
        await pending.complete()

    asyncio.run(go())
    assert TrackingStager.peak_cost <= 2 * unit


def test_over_budget_item_still_runs(tmp_path):
    plugin = FSStoragePlugin(root=str(tmp_path))
    data = os.urandom(5000)
    write_reqs = [
        WriteReq(path="huge", buffer_stager=TrackingStager(data, cost=len(data)))
    ]

    async def go():
        pending = await execute_write_reqs(
            write_reqs, plugin, memory_budget_bytes=10, rank=0
        )
        await pending.complete()

    asyncio.run(go())  # must not deadlock
    assert (tmp_path / "huge").read_bytes() == data


def test_staging_failure_propagates(tmp_path):
    plugin = FSStoragePlugin(root=str(tmp_path))
    write_reqs = [WriteReq(path="x", buffer_stager=FaultyStager())]

    async def go():
        pending = await execute_write_reqs(write_reqs, plugin, 1 << 30, rank=0)
        await pending.complete()

    with pytest.raises(RuntimeError, match="staging boom"):
        asyncio.run(go())


def test_storage_failure_propagates_on_complete(tmp_path):
    plugin = FaultyPlugin(root=str(tmp_path))
    write_reqs = [
        WriteReq(path="x", buffer_stager=TrackingStager(b"abc", cost=3))
    ]

    async def go():
        pending = await execute_write_reqs(write_reqs, plugin, 1 << 30, rank=0)
        await pending.complete()

    with pytest.raises(OSError, match="storage boom"):
        asyncio.run(go())


def test_memory_budget_env_override():
    with override_memory_budget_bytes(12345):
        assert get_process_memory_budget_bytes() == 12345
    budget = get_process_memory_budget_bytes()
    assert 0 < budget <= 32 * 1024**3


def test_read_budget_gating(tmp_path):
    """Reads with consuming cost above budget must still complete (one at a
    time) and all data must arrive."""
    plugin = FSStoragePlugin(root=str(tmp_path))
    blobs = {f"r{i}": os.urandom(500) for i in range(6)}
    loop = asyncio.new_event_loop()
    try:
        for k, v in blobs.items():
            from tpusnap.io_types import WriteIO

            plugin.sync_write(WriteIO(path=k, buf=v), event_loop=loop)
        sink = {}
        read_reqs = [
            ReadReq(path=k, buffer_consumer=ByteConsumer(sink, k, cost=400))
            for k in blobs
        ]
        loop.run_until_complete(execute_read_reqs(read_reqs, plugin, 450, rank=0))
        assert sink == blobs
    finally:
        loop.close()


def test_reporter_stats_and_log_split(tmp_path, caplog, monkeypatch):
    """Reporter parity (reference scheduler.py:96-175): the final summary
    logs the staging-time vs total-time split, periodic reports carry
    per-stage pipeline counts + remaining budget, and the split is
    published via LAST_EXECUTION_STATS."""
    import logging

    from tpusnap import scheduler as sched

    monkeypatch.setattr(sched, "_REPORT_INTERVAL_SEC", 0.0)
    plugin = FSStoragePlugin(root=str(tmp_path))
    write_reqs = [
        WriteReq(path=f"w{i}", buffer_stager=TrackingStager(os.urandom(256), 256))
        for i in range(5)
    ]
    loop = asyncio.new_event_loop()
    try:
        with caplog.at_level(logging.INFO, logger="tpusnap.scheduler"):
            pending = sync_execute_write_reqs(
                write_reqs, plugin, 10_000, rank=0, event_loop=loop
            )
            pending.sync_complete(loop)
    finally:
        loop.close()
    stats = sched.LAST_EXECUTION_STATS["write"]
    assert stats["reqs"] == 5
    assert stats["bytes"] == 5 * 256
    assert stats["staging_s"] is not None
    assert 0 <= stats["staging_s"] <= stats["total_s"]
    text = caplog.text
    assert "staging" in text and "residual I/O" in text
    # Per-stage counts + budget appear in at least one periodic report.
    assert "ready_for_staging=" in text and "io=" in text
    assert "budget" in text


@pytest.mark.parametrize("warm_pool", [False, True])
def test_pooled_buffers_do_not_permanently_debit_budget(tmp_path, warm_pool):
    """ADVICE r4: buffers the staging pool retains after a write must
    not withhold their budget credit — withholding re-debited the same
    resident bytes every reuse cycle, so a budget-capped take whose
    cumulative pooled-clone bytes exceeded the budget degraded to
    fully serialized stage-then-write. The budget governs in-flight
    buffers only (the pool is bounded by its own cap), so staging must
    keep overlapping storage I/O through the whole request list — both
    from a cold pool and from a PRE-WARMED pool (a steady-state
    checkpoint loop's second take: charging parked bytes against the
    take while reuse re-charges them via staging_cost would serialize
    the warm case)."""
    import time

    import tpusnap._staging_pool as sp

    sp.clear()
    unit = 1 << 16
    n = 10
    spans = {}

    class PoolStager(BufferStager):
        def __init__(self, path: str):
            self.path = path

        async def stage_buffer(self, executor=None):
            spans[self.path] = [time.monotonic(), None]
            buf = sp.acquire(unit)
            await asyncio.sleep(0.003)
            return buf

        def get_staging_cost_bytes(self) -> int:
            return unit

    class SlowPlugin(FSStoragePlugin):
        async def write(self, write_io) -> None:
            await asyncio.sleep(0.02)
            await super().write(write_io)
            spans[write_io.path][1] = time.monotonic()

    if warm_pool:
        # Park `n` unit-sized buffers, as a previous take would have.
        parked = [sp.acquire(unit) for _ in range(4)]
        for b in parked:
            assert sp.release(b) is True
        del parked

    plugin = SlowPlugin(root=str(tmp_path))
    write_reqs = [
        WriteReq(path=f"b{i}", buffer_stager=PoolStager(f"b{i}"))
        for i in range(n)
    ]

    async def go():
        pending = await execute_write_reqs(
            write_reqs,
            plugin,
            memory_budget_bytes=2 * unit + unit // 2,
            rank=0,
        )
        await pending.complete()

    try:
        asyncio.run(go())
    finally:
        sp.clear()

    assert all(e is not None for _, e in spans.values())
    # Look only at the SECOND half (by stage start): the old accounting
    # was correct early and only seized up once retained bytes crossed
    # the budget.
    tail = sorted(spans.values())[n // 2 :]
    overlaps = sum(
        1
        for i, a in enumerate(tail)
        for j, b in enumerate(tail)
        if i != j and a[0] < b[1] and b[0] < a[1]
    )
    assert overlaps > 0, (
        "budget-capped pooled take degraded to serialized stage-then-write"
    )


def test_prioritize_staging_defers_io_until_staging_done(tmp_path):
    """Async takes: no storage I/O may start while staging can still
    proceed — write-path CPU inside the staging window is exactly the
    blocked-time the async path exists to avoid. Writes drain via
    PendingIOWork after."""
    import time

    events = []

    class Stager(BufferStager):
        def __init__(self, data):
            self.data = data

        async def stage_buffer(self, executor=None):
            await asyncio.sleep(0.01)
            events.append(("stage", time.monotonic()))
            return self.data

        def get_staging_cost_bytes(self) -> int:
            return len(self.data)

    class Plugin(FSStoragePlugin):
        async def write(self, write_io) -> None:
            events.append(("write", time.monotonic()))
            await super().write(write_io)

    plugin = Plugin(root=str(tmp_path))
    write_reqs = [
        WriteReq(path=f"b{i}", buffer_stager=Stager(os.urandom(64)))
        for i in range(8)
    ]

    async def go():
        pending = await execute_write_reqs(
            write_reqs, plugin, 1 << 30, rank=0, prioritize_staging=True
        )
        assert not pending.scheduler.io_tasks  # nothing dispatched in the window
        assert len(pending.scheduler.ready_for_io) == 8
        await pending.complete()

    asyncio.run(go())
    last_stage = max(t for k, t in events if k == "stage")
    first_write = min(t for k, t in events if k == "write")
    assert first_write >= last_stage, "write started inside the staging window"
    assert sum(1 for k, _ in events if k == "write") == 8


def test_prioritize_staging_budget_starved_opens_io_gate(tmp_path):
    """When the budget cannot hold all staged buffers at once, the I/O
    gate MUST open mid-staging (write completions are the only budget
    source): writes interleave with staging, resident staged bytes stay
    bounded by the budget (plus the ≥1-admission allowance), and the
    take completes. Guards the r5 review finding where the over-budget
    admission fallback kept refilling staging past gated ready-for-io
    buffers, holding every staged buffer resident."""
    import time

    unit = 1000
    events = []
    live = {"n": 0, "peak": 0}

    class Stager(BufferStager):
        def __init__(self, data):
            self.data = data

        async def stage_buffer(self, executor=None):
            await asyncio.sleep(0.005)
            live["n"] += 1
            live["peak"] = max(live["peak"], live["n"])
            events.append(("stage", time.monotonic()))
            return self.data

        def get_staging_cost_bytes(self) -> int:
            return unit

    class Plugin(FSStoragePlugin):
        async def write(self, write_io) -> None:
            events.append(("write", time.monotonic()))
            await super().write(write_io)
            live["n"] -= 1

    plugin = Plugin(root=str(tmp_path))
    write_reqs = [
        WriteReq(path=f"b{i}", buffer_stager=Stager(os.urandom(unit)))
        for i in range(10)
    ]

    async def go():
        pending = await execute_write_reqs(
            write_reqs, plugin, memory_budget_bytes=2 * unit, rank=0,
            prioritize_staging=True,
        )
        await pending.complete()

    asyncio.run(go())
    for i in range(10):
        assert (tmp_path / f"b{i}").exists()
    # The gate opened mid-staging: some write started before staging
    # finished (10 one-unit buffers can never fit a 2-unit budget).
    last_stage = max(t for k, t in events if k == "stage")
    first_write = min(t for k, t in events if k == "write")
    assert first_write < last_stage, "I/O gate never opened under starvation"
    # Resident staged-but-unwritten buffers bounded by the budget (in
    # units) plus the single ≥1-admission allowance.
    assert live["peak"] <= 3, f"budget unenforced: peak {live['peak']} buffers resident"
