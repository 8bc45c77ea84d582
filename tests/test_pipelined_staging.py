"""Pipelined chunk-grain async staging (fast tier-1 suite, marker
``pipelined``).

The contract under test: an ``async_take`` of a state larger than
TPUSNAP_ASYNC_STAGE_WINDOW_BYTES returns control after staging ONE
window — blocked time and resident clone bytes are O(window), the
residual windows clone on the background drain interleaved with their
storage I/O, and the committed snapshot is bit-exact regardless. Plus
the opt-in COW mode (hash-verify-at-write instead of cloning) and the
``async_blocked_s`` history/regression wiring.
"""

import asyncio
import glob
import os
import time

import numpy as np
import pytest

from tpusnap import PytreeState, Snapshot, StateDict
from tpusnap import telemetry as tele_mod
from tpusnap.io_types import BufferStager, WriteReq
from tpusnap.knobs import (
    override_async_cow,
    override_async_stage_window_bytes,
    override_batching_disabled,
    override_journal_disabled,
    override_memory_budget_bytes,
    override_stage_threads,
)
from tpusnap.scheduler import execute_write_reqs
from tpusnap.storage_plugins.fs import FSStoragePlugin

pytestmark = pytest.mark.pipelined

_N = 8
_PER = 1 << 18  # 256 KiB per array; async staging cost is 2x


def _state(n=_N, per=_PER, seed=7):
    return {
        f"w{i}": np.random.default_rng(seed * 100 + i)
        .integers(0, 255, per, dtype=np.uint8)
        .view(np.float32)
        for i in range(n)
    }


def _blob_files(root):
    return [
        f
        for f in glob.glob(os.path.join(root, "**", "*"), recursive=True)
        if os.path.isfile(f)
        and ".tpusnap" not in f.split(os.sep)
        and not f.endswith(".snapshot_metadata")
    ]


def _restore_and_check(path, state):
    tgt = {"m": PytreeState({k: np.zeros_like(v) for k, v in state.items()})}
    Snapshot(path).restore(tgt)
    for k, v in state.items():
        assert np.array_equal(tgt["m"].tree[k].view(np.uint8), v.view(np.uint8)), k


# ------------------------------------------------------ scheduler-level


class _UnitStager(BufferStager):
    live = 0
    peak = 0

    def __init__(self, data):
        self.data = data

    async def stage_buffer(self, executor=None):
        _UnitStager.live += 1
        _UnitStager.peak = max(_UnitStager.peak, _UnitStager.live)
        await asyncio.sleep(0.002)
        return self.data

    def get_staging_cost_bytes(self) -> int:
        return len(self.data)


def test_pipelined_execute_returns_at_first_window(tmp_path):
    """The engine hands back a resumable PendingIOWork once one window's
    worth of staging cost is staged; complete() stages the rest under
    the window bound and writes everything."""
    _UnitStager.live = 0
    _UnitStager.peak = 0
    unit = 1000

    class DecPlugin(FSStoragePlugin):
        async def write(self, write_io) -> None:
            await asyncio.sleep(0.005)
            await super().write(write_io)
            _UnitStager.live -= 1

    plugin = DecPlugin(root=str(tmp_path))
    write_reqs = [
        WriteReq(path=f"b{i}", buffer_stager=_UnitStager(os.urandom(unit)))
        for i in range(10)
    ]

    async def go():
        pending = await execute_write_reqs(
            write_reqs,
            plugin,
            memory_budget_bytes=1 << 30,
            rank=0,
            pipelined_staging=True,
        )
        # Window = 2 units: staging must NOT have completed at return.
        assert not pending.staging_complete()
        staged_at_return = _UnitStager.peak
        assert staged_at_return <= 3  # window (2) + the >=1 admission
        await pending.complete()
        assert pending.staging_complete()

    with override_async_stage_window_bytes(2 * unit):
        asyncio.run(go())
    for i in range(10):
        assert (tmp_path / f"b{i}").exists()
    # Resident staged-but-unwritten buffers stayed window-bounded
    # through the drain too.
    assert _UnitStager.peak <= 3, f"window unenforced: peak {_UnitStager.peak}"


def test_pipelined_stage_eagerly_requests_stage_in_blocked_window(tmp_path):
    """Requests selected by stage_eagerly (stage-time manifest
    annotators on multi-process takes) stage before control returns,
    even past the window target."""
    staged = []

    class S(BufferStager):
        def __init__(self, name, data):
            self.name = name
            self.data = data

        async def stage_buffer(self, executor=None):
            staged.append(self.name)
            return self.data

        def get_staging_cost_bytes(self) -> int:
            return len(self.data)

    plugin = FSStoragePlugin(root=str(tmp_path))
    write_reqs = [
        WriteReq(path=f"e{i}", buffer_stager=S(f"e{i}", os.urandom(500)))
        for i in range(4)
    ] + [
        WriteReq(path=f"d{i}", buffer_stager=S(f"d{i}", os.urandom(500)))
        for i in range(4)
    ]

    async def go():
        pending = await execute_write_reqs(
            write_reqs,
            plugin,
            memory_budget_bytes=1 << 30,
            rank=0,
            pipelined_staging=True,
            stage_eagerly=lambda wr: wr.path.startswith("e"),
        )
        at_return = list(staged)
        assert {f"e{i}" for i in range(4)} <= set(at_return), at_return
        await pending.complete()

    with override_async_stage_window_bytes(1000):
        asyncio.run(go())


def test_stage_eagerly_holds_window_open_across_threads(tmp_path):
    """Completed NON-eager stagers must not count against the eager
    set: with TPUSNAP_STAGE_THREADS=2, fast non-eager stagers that
    overshoot the window target while a slow eager stager is still in
    flight may not close the blocked window early."""
    staged = []

    class S(BufferStager):
        def __init__(self, name, data, delay):
            self.name = name
            self.data = data
            self.delay = delay

        async def stage_buffer(self, executor=None):
            await asyncio.sleep(self.delay)
            staged.append(self.name)
            return self.data

        def get_staging_cost_bytes(self) -> int:
            return len(self.data)

    plugin = FSStoragePlugin(root=str(tmp_path))
    # One slow eager annotator + fast non-eager bulk whose cost alone
    # exceeds the window target.
    write_reqs = [
        WriteReq(path="eager", buffer_stager=S("eager", os.urandom(400), 0.15))
    ] + [
        WriteReq(path=f"d{i}", buffer_stager=S(f"d{i}", os.urandom(600), 0.001))
        for i in range(6)
    ]

    async def go():
        pending = await execute_write_reqs(
            write_reqs,
            plugin,
            memory_budget_bytes=1 << 30,
            rank=0,
            pipelined_staging=True,
            stage_eagerly=lambda wr: wr.path == "eager",
        )
        assert "eager" in staged, f"window closed mid-eager: {staged}"
        await pending.complete()

    with override_stage_threads(2), override_async_stage_window_bytes(1200):
        asyncio.run(go())


# ------------------------------------------------------- take-level (a)


def test_blocked_window_is_budget_bounded(tmp_path):
    """Satellite (a): an async take of N windows under a tight memory
    budget keeps peak staged bytes <= budget (budget high-water gauge)
    and returns control BEFORE all blobs exist on disk; the commit then
    completes and restores bit-exact."""
    state = _state()
    budget = 2 * 2 * _PER  # two in-flight clones (async cost is 2x)
    path = str(tmp_path / "snap")
    with override_batching_disabled(True), override_journal_disabled(
        True
    ), override_memory_budget_bytes(budget):
        pending = Snapshot.async_take(
            "chaos+fs://" + path,
            {"m": PytreeState(state)},
            # Every write stalls 0.6 s inside the op: nothing can land
            # on disk within the blocked window's return path.
            storage_options={"fault_plan": {"stall_op": ("write", 0, 0.6)}},
        )
        # Control is back before the drain produced all blobs (or any
        # metadata): the pipelined window is doing its job.
        assert not os.path.exists(os.path.join(path, ".snapshot_metadata"))
        assert len(_blob_files(path)) < _N
        snap = pending.wait()
        assert pending.staged()
    summary = tele_mod.LAST_TAKE_SUMMARY
    high_water = summary["gauges"]["scheduler.budget_used_bytes"]
    assert high_water <= budget, (high_water, budget)
    assert summary["counters"]["scheduler.bytes_staged"] == _N * _PER
    assert os.path.exists(os.path.join(path, ".snapshot_metadata"))
    _restore_and_check(snap.path, state)


def test_window_fits_state_keeps_strict_semantics(tmp_path):
    """States at or under the window stage COMPLETELY inside the
    blocked window — the pre-pipeline consistency contract (mutate
    in place right after return) holds exactly, as does window=0."""
    state = _state(n=3)
    # override_async_cow(False): "mutate right after return" is the
    # defensive-CLONE contract; the default COW mode's contract is
    # wait_staged() (covered in the COW section below).
    for window in (1 << 30, 0):
        path = str(tmp_path / f"snap{window}")
        with override_async_stage_window_bytes(window), override_async_cow(
            False
        ):
            pending = Snapshot.async_take(path, {"m": PytreeState(state)})
            assert pending.staged()  # frozen before control returned
            # "Training step": in-place mutation while I/O drains.
            mutated = {k: v.copy() for k, v in state.items()}
            for v in state.values():
                v.view(np.uint8)[:] = 0xAB
            pending.wait()
            _restore_and_check(path, mutated)
            for k, v in mutated.items():  # restore sources for next loop
                state[k][:] = v


def test_stall_in_drain_does_not_extend_blocked_window(tmp_path):
    """Satellite (c): a chaos ``stall`` fault on every storage write
    (the background drain's leg) must not extend the blocked window —
    writes are gated out of it entirely."""
    state = _state()
    stall_s = 1.2
    path = str(tmp_path / "snap")
    with override_batching_disabled(True), override_journal_disabled(
        True
    ), override_async_stage_window_bytes(2 * 2 * _PER):
        t0 = time.perf_counter()
        pending = Snapshot.async_take(
            "chaos+fs://" + path,
            {"m": PytreeState(state)},
            storage_options={
                "fault_plan": {"stall_op": ("write", 0, stall_s)}
            },
        )
        blocked = time.perf_counter() - t0
        pending.wait()
    assert blocked < stall_s, (
        f"blocked window {blocked:.2f}s swallowed the drain's "
        f"{stall_s}s write stall"
    )
    summary = tele_mod.LAST_TAKE_SUMMARY
    assert summary["async_blocked_s"] < stall_s
    _restore_and_check(path, state)


def test_single_stage_thread_by_default(tmp_path, monkeypatch):
    """Satellite: the clone executor is sized by TPUSNAP_STAGE_THREADS
    (default 1 — interleaved clone threads measured slower than one),
    not hardcoded."""
    from tpusnap.knobs import get_stage_threads
    from tpusnap.scheduler import _WriteScheduler

    # The ambient environment may legitimately set the knob (TPU-VM
    # operators are told to); the DEFAULT is what's under test.
    monkeypatch.delenv("TPUSNAP_STAGE_THREADS", raising=False)
    assert get_stage_threads() == 1
    with override_stage_threads(3):
        sched = _WriteScheduler(
            [], FSStoragePlugin(root=str(tmp_path)), 1 << 20, rank=0
        )
        try:
            assert sched.stage_concurrency == 3
            assert sched.executor._max_workers == 3
        finally:
            sched.executor.shutdown(wait=False)
            sched.hash_executor.shutdown(wait=False)


def test_warm_pool_reuse_across_windows(tmp_path):
    """Steady-state windows allocate nothing: window N+1's clones reuse
    the buffers window N's writes released (pool high-water stays at
    about one window, not the state size)."""
    import tpusnap._staging_pool as sp

    sp.clear()
    state = _state()
    path = str(tmp_path / "snap")
    # Clone mode: the pool LIFO contract under test only exists when
    # staging clones (the default COW mode clones nothing).
    with override_batching_disabled(True), override_journal_disabled(
        True
    ), override_async_stage_window_bytes(2 * 2 * _PER), override_async_cow(
        False
    ):
        Snapshot.async_take(path, {"m": PytreeState(state)}).wait()
    try:
        # All clones parked back; far fewer distinct buffers than blobs.
        assert 0 < sp.free_bytes() < _N * _PER, sp.free_bytes()
    finally:
        sp.clear()
    _restore_and_check(path, state)


# ------------------------------------- who counts towards the window


class _Gate:
    """Holds every device leaf's (and device slab's) staging until
    ``open()``: whatever returns while it is shut waited for none."""

    def __init__(self, monkeypatch):
        import threading

        from tpusnap.batcher import DeviceBatchedBufferStager
        from tpusnap.io_preparers.array import ArrayBufferStager

        self.event = threading.Event()
        self.entered = []
        gate = self

        def gated(cls, name_of):
            orig = cls._stage_blocking

            def _stage_blocking(stager):
                if not isinstance(getattr(stager, "arr", None), np.ndarray):
                    gate.entered.append(name_of(stager))
                    assert gate.event.wait(30), "gate never opened"
                return orig(stager)

            monkeypatch.setattr(cls, "_stage_blocking", _stage_blocking)

        gated(ArrayBufferStager, lambda s: s.entry.location)
        gated(DeviceBatchedBufferStager, lambda s: "slab")

    def open(self):
        self.event.set()


@pytest.fixture
def device_leaves(monkeypatch):
    """CPU-backend arrays that answer as an accelerator's do: np.asarray
    of them is said not to alias the device buffer."""
    import tpusnap.io_preparers.array as array_mod

    monkeypatch.setattr(
        array_mod, "_asarray_aliases_device_buffer", lambda device: False
    )


def _device_state(n=4, per=_PER, seed=11, sharded=False):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    state = {k: jnp.asarray(v) for k, v in _state(n, per, seed).items()}
    if sharded:  # each leaf cut in four shards along its one axis
        mesh = Mesh(np.array(jax.devices()[:4]), ("x",))
        rows = NamedSharding(mesh, PartitionSpec("x"))
        state = {k: jax.device_put(v, rows) for k, v in state.items()}
    return state


def _counters():
    return tele_mod.LAST_TAKE_SUMMARY["counters"]


@pytest.mark.parametrize(
    "window,batching,sharded",
    [
        (_PER, False, False),
        (1 << 30, False, False),
        (1 << 30, True, False),
        (_PER, False, True),
    ],
    ids=["over_window", "under_window", "device_slab", "shards"],
)
def test_device_leaves_are_staged_behind_the_return(
    tmp_path, monkeypatch, device_leaves, window, batching, sharded
):
    """(a) A state of device leaves, larger or smaller than the window,
    whole, packed into a slab or cut into shards: async_take returns
    with nothing staged and nothing held, and the snapshot is the state
    bit for bit."""
    state = _device_state(sharded=sharded)
    want = {k: np.asarray(v).copy() for k, v in state.items()}
    gate = _Gate(monkeypatch)
    path = str(tmp_path / "snap")
    with override_batching_disabled(not batching), override_async_stage_window_bytes(
        window
    ):
        pending = Snapshot.async_take(path, {"m": PytreeState(state)})
        # Back although no leaf could be staged: the gate is shut.
        assert not pending._pending_io_work.staging_complete()
        assert not pending.staged()
        gate.open()
        assert pending.wait_staged(timeout=30)
        pending.wait()
    assert gate.entered  # the drain staged them, through the gate
    assert _counters().get("scheduler.window_held_bytes", 0) == 0
    assert _counters()["scheduler.window_released_bytes"] == 4 * _PER
    assert _counters()["scheduler.bytes_staged"] == 4 * _PER
    _restore_and_check(path, want)


def test_numpy_leaves_hold_the_window_device_leaves_do_not(
    tmp_path, monkeypatch, device_leaves
):
    """(b) numpy leaves beside device leaves: the numpy ones are cloned
    before the return (an in-place write right after it does not reach
    the snapshot), the device ones are staged behind it."""
    host = _state(n=3, seed=5)
    device = {f"d{i}": v for i, v in enumerate(_device_state(n=3).values())}
    want = {k: np.asarray(v).copy() for k, v in {**host, **device}.items()}
    gate = _Gate(monkeypatch)
    path = str(tmp_path / "snap")
    # The clone contract (COW's is wait_staged(), see the COW section).
    with override_batching_disabled(True), override_async_cow(False):
        pending = Snapshot.async_take(path, {"m": PytreeState({**host, **device})})
        assert not pending._pending_io_work.staging_complete()
        for v in host.values():
            v.view(np.uint8)[:] = 0xAB
        gate.open()
        pending.wait()
    assert len(gate.entered) == 3
    # Staging cost, which in clone mode is twice a leaf's bytes.
    assert _counters()["scheduler.window_held_bytes"] == 3 * 2 * _PER
    assert _counters()["scheduler.window_released_bytes"] == 3 * 2 * _PER
    _restore_and_check(path, want)


@pytest.mark.parametrize("mode", ["window_0", "incremental"])
def test_strict_modes_stage_device_leaves_before_the_return(
    tmp_path, device_leaves, mode
):
    """(c) Window 0 and an incremental take are not pipelined: every
    leaf, device leaves too, is staged when async_take returns."""
    state = _device_state()
    want = {k: np.asarray(v).copy() for k, v in state.items()}
    path = str(tmp_path / "snap")
    kwargs = {}
    if mode == "incremental":
        kwargs["incremental_from"] = str(tmp_path / "base")
        Snapshot.take(
            kwargs["incremental_from"],
            {"m": PytreeState(state)},
            _record_dedup_hashes=True,
        )
    with override_batching_disabled(True), override_async_stage_window_bytes(
        0 if mode == "window_0" else 1 << 30
    ):
        pending = Snapshot.async_take(path, {"m": PytreeState(state)}, **kwargs)
        assert pending._pending_io_work.staging_complete()
        pending.wait()
    assert _counters()["scheduler.window_held_bytes"] > 0
    assert _counters().get("scheduler.window_released_bytes", 0) == 0
    _restore_and_check(path, want)


class _NamedStager(BufferStager):
    """Stages at once and says where it was staged from; ``aliases`` is
    the answer it gives the window."""

    staged: list = []

    def __init__(self, name, aliases):
        self.name = name
        self.aliases = aliases
        self.data = os.urandom(500)

    async def stage_buffer(self, executor=None):
        _NamedStager.staged.append(self.name)
        return self.data

    def get_staging_cost_bytes(self) -> int:
        return len(self.data)

    def aliases_caller_memory(self) -> bool:
        return self.aliases


class _DuckStager:
    """No BufferStager and no ``aliases_caller_memory``: a plugin's
    stager from before the method existed."""

    def __init__(self, name):
        self.name = name
        self.data = os.urandom(500)

    async def stage_buffer(self, executor=None):
        _NamedStager.staged.append(self.name)
        return self.data

    def get_staging_cost_bytes(self) -> int:
        return len(self.data)


@pytest.mark.parametrize(
    "held,eager",
    [
        ([], ["e0", "e1"]),
        (["h0", "h1"], []),
        (["h0"], ["e0"]),
        (["duck0", "duck1"], []),
        ([], []),
    ],
    ids=["eager_set", "aliasing", "both", "no_method", "nothing_held"],
)
def test_window_holds_only_eager_and_aliasing_requests(tmp_path, held, eager):
    """(d), (f) Of a pipelined take's requests the return waits for the
    stage_eagerly set and for those that alias caller memory (a stager
    without the method among them), whatever the window's size; every
    other request is staged by the drain."""
    _NamedStager.staged = []
    released = [f"r{i}" for i in range(4)]

    def make(name):
        if name.startswith("duck"):
            return _DuckStager(name)
        return _NamedStager(name, aliases=name.startswith("h"))

    write_reqs = [
        WriteReq(path=name, buffer_stager=make(name))
        for name in released[:2] + eager + held + released[2:]
    ]

    async def go():
        pending = await execute_write_reqs(
            write_reqs,
            FSStoragePlugin(root=str(tmp_path)),
            memory_budget_bytes=1 << 30,
            rank=0,
            pipelined_staging=True,
            stage_eagerly=lambda wr: wr.path.startswith("e"),
        )
        at_return = set(_NamedStager.staged)
        assert set(eager + held) <= at_return, at_return
        # One request may have been dispatched beside them; never all.
        assert not set(released) <= at_return, at_return
        assert not pending.staging_complete()
        await pending.complete()
        assert pending.staging_complete()

    with override_async_stage_window_bytes(1 << 20):
        asyncio.run(go())
    assert sorted(_NamedStager.staged) == sorted(eager + held + released)
    for name in eager + held + released:
        assert (tmp_path / name).exists()


@pytest.mark.parametrize("rendezvous", [False, True], ids=["donated", "wait_staged"])
def test_leaf_deleted_before_it_is_staged_fails_by_name(
    tmp_path, monkeypatch, device_leaves, rendezvous
):
    """(e) A device leaf that a step donates (deletes) before the drain
    has staged it fails the take with the leaf's name and the way out,
    and commits nothing; after wait_staged() the same delete is safe."""
    state = _device_state()
    want = {k: np.asarray(v).copy() for k, v in state.items()}
    gate = _Gate(monkeypatch)
    path = str(tmp_path / "snap")
    with override_batching_disabled(True), override_journal_disabled(True):
        pending = Snapshot.async_take(path, {"m": PytreeState(state)})
        if rendezvous:
            gate.open()
            assert pending.wait_staged(timeout=30)
        state["w2"].delete()  # what donating it to a jitted step does
        gate.open()
        if rendezvous:
            pending.wait()
        else:
            with pytest.raises(RuntimeError, match=r"w2.*wait_staged\(\)"):
                pending.wait()
    if rendezvous:
        _restore_and_check(path, want)
    else:
        assert not os.path.exists(os.path.join(path, ".snapshot_metadata"))


# ------------------------------------------------------------ COW mode


def test_cow_frozen_state_clones_nothing(tmp_path):
    """TPUSNAP_ASYNC_COW: unmutated (frozen) arrays are written straight
    from live memory — the staging pool sees zero clone traffic — and
    the hash-verify-at-write pass accepts them."""
    import tpusnap._staging_pool as sp

    sp.clear()
    state = _state()
    path = str(tmp_path / "snap")
    with override_batching_disabled(True), override_async_cow(True):
        pending = Snapshot.async_take(path, {"m": PytreeState(state)})
        snap = pending.wait()
    assert sp.free_bytes() == 0  # no clone buffers were ever acquired
    summary = tele_mod.LAST_TAKE_SUMMARY
    assert summary["stages"].get("cow_verify", {}).get("count") == _N
    _restore_and_check(snap.path, state)


def test_cow_detects_concurrent_mutation(tmp_path):
    """TPUSNAP_ASYNC_COW: mutating an array between staging (hash
    recorded) and its storage write fails the take loudly — the
    metadata is never committed, torn bytes are never silently blessed."""
    state = _state(n=4)
    path = str(tmp_path / "snap")
    with override_batching_disabled(True), override_journal_disabled(
        True
    ), override_async_cow(True), override_async_stage_window_bytes(
        2 * _PER
    ):
        pending = Snapshot.async_take(
            "chaos+fs://" + path,
            {"m": PytreeState(state)},
            # Every write stalls 1 s: the mutation below lands before
            # any write reads the live bytes.
            storage_options={"fault_plan": {"stall_op": ("write", 0, 1.0)}},
        )
        # COW-aware rendezvous: staging per-se is done (no clones) but
        # the live bytes stay aliased until the stalled writes drain —
        # staged()/wait_staged() must NOT report safe-to-mutate yet.
        assert not pending.wait_staged(timeout=0.05)
        assert not pending.staged()
        for v in state.values():
            v.view(np.uint8)[:] = 0x5A
        with pytest.raises(RuntimeError, match="concurrent mutation"):
            pending.wait()
    assert not os.path.exists(os.path.join(path, ".snapshot_metadata"))


def test_cow_verify_checks_xxh64_lane():
    """verify_cow_after_write re-verifies the 64-bit dedup lane when
    recorded — a mutation that (hypothetically) collides the 32-bit
    CRC lane is still caught."""
    from tpusnap import _native
    from tpusnap.io_preparers.array import ArrayBufferStager, _record_checksums
    from tpusnap.manifest import TensorEntry

    data = np.arange(1024, dtype=np.uint8)
    entry = TensorEntry(
        location="w", serializer="buffer_protocol", dtype="uint8",
        shape=[1024], replicated=False, byte_range=None,
    )
    _record_checksums(entry, memoryview(data.tobytes()), True)
    assert entry.dedup_hash or entry.tile_dedup_hashes
    stager = ArrayBufferStager(data, is_async_snapshot=True, entry=entry)
    stager.verify_cow_after_write(data.tobytes())  # unmutated: clean
    mutated = bytearray(data.tobytes())
    mutated[0] ^= 0xFF
    with pytest.raises(_native.ChecksumError):
        # Bypass the CRC lane: the xxh lane alone must catch it.
        stager._verify_cow_xxh_lane(memoryview(bytes(mutated)))


def test_cow_slab_members_verified_against_slab_copy(tmp_path, monkeypatch):
    """COW + batching: slab members return LIVE bytes and the slab copy
    is their effective clone — the fill pass must verify the copy
    against the stage-time hash (the write pipeline only sees the slab
    stager's cow_pending), so a mutation between the member's hash pass
    and the slab copy fails the take loudly."""
    from tpusnap.io_preparers.array import ArrayBufferStager

    # Happy path: small arrays pack into a slab, COW members verify
    # clean against their slab copy, take commits and restores.
    state = _state(n=4)
    path = str(tmp_path / "ok")
    with override_async_cow(True):
        snap = Snapshot.async_take(path, {"m": PytreeState(state)}).wait()
    _restore_and_check(snap.path, state)

    # Mutation between the member's hash pass and the slab copy: wrap
    # stage_buffer to mutate the live array right after the hash is
    # recorded (deterministic — no timing race).
    orig = ArrayBufferStager.stage_buffer

    async def mutate_after_hash(self, executor=None):
        buf = await orig(self, executor)
        if getattr(self, "cow_pending", False):
            np.asarray(self.arr).view(np.uint8)[:1] ^= 0xFF
        return buf

    monkeypatch.setattr(ArrayBufferStager, "stage_buffer", mutate_after_hash)
    bad = str(tmp_path / "bad")
    with override_async_cow(True), override_journal_disabled(True):
        with pytest.raises(RuntimeError, match="concurrent mutation"):
            Snapshot.async_take(bad, {"m": PytreeState(_state(n=4))}).wait()
    assert not os.path.exists(os.path.join(bad, ".snapshot_metadata"))


# -------------------------------------------------- history/regression


def test_async_blocked_s_recorded_and_gated(tmp_path):
    """Satellite: async_blocked_s lands in the take summary and the
    history event, and `history --check` grades it as a duration
    (upward regressions fire)."""
    from tpusnap import check_regression
    from tpusnap import history as hist
    from tpusnap.knobs import override_telemetry_dir

    state = _state(n=2)
    with override_telemetry_dir(str(tmp_path / "tele")):
        hist._reset_process_state()
        Snapshot.async_take(str(tmp_path / "s"), {"m": PytreeState(state)}).wait()
        events = hist.load_history()
        takes = [e for e in events if e.get("kind") == "take"]
        assert takes and isinstance(takes[-1].get("async_blocked_s"), float)

        # Synthetic trend: a 2x slower blocked window must regress.
        base = dict(takes[-1], cold=False)
        evs = []
        for i in range(5):
            evs.append(dict(base, async_blocked_s=0.1, ts=i))
        evs.append(dict(base, async_blocked_s=0.25, ts=9))
        report = check_regression(
            evs, kind="take", metric="async_blocked_s", min_baseline=3
        )
        assert report.ok and report.regressed, report.reason
        ok = check_regression(
            evs[:-1], kind="take", metric="async_blocked_s", min_baseline=3
        )
        assert ok.ok and not ok.regressed, ok.reason
