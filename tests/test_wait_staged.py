"""What a caller that waits for the staging waits for (PR 52).

``PendingSnapshot.staged()`` / ``wait_staged()`` are read off what the take
did, not off ``TPUSNAP_ASYNC_COW``: staging-complete where no stager went
copy-on-write, this rank's write drain where one did. While the caller
waits, a leaf that crosses as it lies is not charged to the staging budget
(its host value lives on the caller's own array: a write's end gives
nothing back), and the copies are started four times as far ahead as
beside a caller that may step (``tests/test_dtoh_lookahead.py`` holds the
depth).

A CPU backend's arrays alias and always go copy-on-write, so these tests
say of them what an accelerator's say of themselves (``np.asarray`` does
not alias; the device reports free memory) and, where a test deletes or
donates the arrays, land a real host copy as an accelerator's transfer
does. Counts, orders and bytes on the CPU, never a time."""

import asyncio
import contextlib
import io
import json
import os
import threading
import time
from contextlib import redirect_stdout

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpusnap import PytreeState, Snapshot, telemetry
from tpusnap.batcher import BatchedBufferStager, DeviceBatchedBufferStager
from tpusnap.io_preparers import array as array_preparer
from tpusnap.io_preparers.array import ArrayBufferStager
from tpusnap.io_types import WriteReq, stager_went_cow
from tpusnap.knobs import (
    override_async_cow,
    override_async_stage_window_bytes,
    override_batching_disabled,
    override_stage_threads,
)
from tpusnap.manifest import TensorEntry
from tpusnap.scheduler import PendingIOWork, _WriteScheduler
from tpusnap.serialization import dtype_to_string
from tpusnap.storage_plugins.fs import FSStoragePlugin

FLOOR = 1 << 16  # the owned crossing's floor in these tests
LEAF = (96, 256)  # float32: 96 KiB, over the floor


def _values(shape, seed, dtype=np.float32):
    return np.random.default_rng(seed).standard_normal(shape).astype(dtype)


@pytest.fixture()
def on_an_accelerator(monkeypatch):
    """Every jax.Array answers as an accelerator's: ``np.asarray`` of it
    is said not to alias the device's buffer, and its device has room."""
    monkeypatch.setattr(array_preparer, "_asarray_aliases_device_buffer", lambda device: False)
    monkeypatch.setattr(array_preparer, "_device_free_bytes", lambda device: 1 << 40)
    monkeypatch.setattr(array_preparer, "RELAYOUT_MIN_BYTES", FLOOR)


@pytest.fixture()
def transfers_land_copies(monkeypatch):
    """A device leaf's host value is a copy of its own, kept with the
    stager that fetched it, as an accelerator's transfer lands one (on
    this backend ``np.asarray`` is a view of the buffer that a donating
    step reuses)."""
    plain = ArrayBufferStager.host_array

    def host_array(self):
        if not isinstance(self.arr, jax.Array):
            return plain(self)
        if "landed" not in self.__dict__:
            self.landed = np.array(plain(self))
        return self.landed

    monkeypatch.setattr(ArrayBufferStager, "host_array", host_array)


class _StagingHeldUntilTheCallerWaits:
    """No leaf is staged before a thread stands in ``wait_staged()``: what
    the drain does between ``async_take``'s return and the caller's
    arrival is then the same in every run (on the chip a leaf takes tens
    of milliseconds to cross; here it takes none)."""

    def __init__(self, monkeypatch):
        self.event = threading.Event()
        plain, held = ArrayBufferStager._stage_blocking, self

        def _stage_blocking(stager):
            assert held.event.wait(30), "nobody came to wait"
            return plain(stager)

        monkeypatch.setattr(ArrayBufferStager, "_stage_blocking", _stage_blocking)

    def watch(self, pending):
        scheduler = pending._pending_io_work.scheduler

        def body():
            while not scheduler.callers_waiting:
                time.sleep(0.001)
            self.event.set()

        threading.Thread(target=body, daemon=True).start()


class _HeldWrites:
    """Every blob's write waits until ``open()``; what returns while it is
    shut waited for no write."""

    def __init__(self, monkeypatch):
        self.event = threading.Event()
        self.entered = 0
        plain, held = FSStoragePlugin.write, self

        async def write(plugin, write_io):
            if not write_io.path.startswith(".tpusnap"):
                held.entered += 1
                while not held.event.is_set():
                    await asyncio.sleep(0.002)
            await plain(plugin, write_io)

        monkeypatch.setattr(FSStoragePlugin, "write", write)

    def open(self):
        self.event.set()


def _files(root):
    """Every blob of a snapshot by its path, and the manifest's entries."""
    blobs = {}
    for base, dirs, names in os.walk(root):
        dirs[:] = [d for d in dirs if d != ".tpusnap"]
        for name in names:
            if name != ".snapshot_metadata":
                path = os.path.join(base, name)
                with open(path, "rb") as f:
                    blobs[os.path.relpath(path, root)] = f.read()
    with open(os.path.join(root, ".snapshot_metadata")) as f:
        manifest = json.load(f)["manifest"]
    return blobs, manifest


def _device(n, seed=0, shape=LEAF):
    return {f"d{i}": jnp.asarray(_values(shape, seed + i)) for i in range(n)}


def _numpy(n, seed=50, shape=LEAF):
    return {f"h{i}": _values(shape, seed + i) for i in range(n)}


# ------------------------------------------------- (1) what staged() means

# state, batching, TPUSNAP_ASYNC_COW -> does a stager go copy-on-write?
STATES = {
    "device_leaves": (lambda: _device(4), False, True, False),
    "a_device_slab": (lambda: {**_device(2), **_device(4, seed=20, shape=(256,))}, True, True, False),
    "a_numpy_leaf": (lambda: {**_device(3), **_numpy(1)}, False, True, True),
    "a_slab_with_numpy_members": (lambda: {**_device(3), **_numpy(3, shape=(256,))}, True, True, True),
    "a_numpy_leaf_cloned": (lambda: {**_device(3), **_numpy(1)}, False, False, False),
}


@pytest.mark.parametrize("case", sorted(STATES))
def test_wait_staged_returns_at_staging_complete_unless_a_stager_went_cow(
    tmp_path, monkeypatch, on_an_accelerator, case
):
    """With every write held back: a take none of whose stagers went
    copy-on-write lets its caller go once the state is on the host (an
    accelerator's leaves, whole or in a device slab; a numpy leaf that was
    cloned); one numpy leaf, or a host slab with numpy members, written
    from the live bytes keeps the caller until this rank's writes have
    drained, as every take did before."""
    make, batching, cow, went_cow = STATES[case]
    state = make()
    want = {k: np.array(v) for k, v in state.items()}
    writes = _HeldWrites(monkeypatch)
    monkeypatch.setenv("TPUSNAP_SLAB_SIZE_THRESHOLD_BYTES", str(1 << 15))
    with override_batching_disabled(not batching), override_async_cow(cow):
        pending = Snapshot.async_take(str(tmp_path / "snap"), {"m": PytreeState(state)})
        io_work = pending._pending_io_work
        assert io_work.wait_staged(30)  # the state is on the host
        assert io_work.went_cow() is went_cow
        assert not io_work.drained()
        if went_cow:
            assert not pending.wait_staged(timeout=0.2) and not pending.staged()
            assert not io_work.safe_to_mutate()
        else:
            assert pending.wait_staged(timeout=30) and pending.staged()
            assert not io_work.drained()  # and no write has ended
        writes.open()
        assert pending.wait_staged(timeout=30) and pending.staged()
        pending.wait()
        assert io_work.drained() and io_work.safe_to_mutate() and writes.entered > 0
    target = {"m": PytreeState({k: np.zeros_like(v) for k, v in want.items()})}
    Snapshot(str(tmp_path / "snap")).restore(target)
    for k, v in want.items():
        assert np.asarray(target["m"].tree[k]).tobytes() == v.tobytes(), k


@pytest.mark.parametrize("slab", [BatchedBufferStager, DeviceBatchedBufferStager])
def test_a_slab_says_whether_it_took_live_bytes(monkeypatch, slab):
    """A host slab whose member handed over the caller's live bytes under
    copy-on-write says so after staging (the member's own flag is spent
    on the slab's copy); a device slab that packed on the device took
    none, and says what its host fallback took where the pack failed."""
    leaves = [_values((256,), i) for i in range(3)]
    if slab is DeviceBatchedBufferStager:
        leaves = [jnp.asarray(x) for x in leaves]
    members, offset = [], 0
    for i, leaf in enumerate(leaves):
        entry = TensorEntry(location=f"0/b{i}", serializer="buffer_protocol",
                            dtype=dtype_to_string(leaf.dtype), shape=list(leaf.shape), replicated=False)
        members.append((offset, leaf.nbytes, ArrayBufferStager(leaf, is_async_snapshot=True, entry=entry)))
        offset += leaf.nbytes
    packed = slab(members)
    assert not stager_went_cow(packed)
    asyncio.run(packed.stage_buffer())
    assert stager_went_cow(packed) is (slab is BatchedBufferStager)
    if slab is DeviceBatchedBufferStager:
        import tpusnap.batcher as batcher_mod

        def fails(arrs):
            raise RuntimeError("no pack today")

        monkeypatch.setattr(batcher_mod, "_pack_on_device", fails)
        fallen = slab(members)
        asyncio.run(fallen.stage_buffer())
        assert stager_went_cow(fallen)  # a CPU backend's arrays: live bytes, copy-on-write
    assert not any(m.cow_pending for _, _, m in members)


# ------------------------------------------------- (2) what the budget charges


def _entry(arr, location):
    return TensorEntry(location=location, serializer="buffer_protocol",
                       dtype=dtype_to_string(arr.dtype), shape=list(arr.shape), replicated=False)


def _leaf_stager(arr, location):
    return ArrayBufferStager(arr, is_async_snapshot=True, entry=_entry(arr, location))


def _swapped(values):
    """A device array whose minor dimension is its first: how a TPU lays
    a leaf out whose last dimension is no multiple of 128, and what its
    host value is turned from."""
    from jax.experimental.layout import Format, Layout

    dev = jax.devices()[0]
    arr = jax.device_put(values, Format(Layout(major_to_minor=(1, 0)), jax.sharding.SingleDeviceSharding(dev)))
    assert not np.asarray(arr).flags.c_contiguous  # the host value keeps the device's order
    return arr


def _slab_of_device_leaves():
    members, offset = [], 0
    for i in range(3):
        leaf = jnp.asarray(_values((96, 64), 70 + i))
        members.append((offset, leaf.nbytes, _leaf_stager(leaf, f"0/s{i}")))
        offset += leaf.nbytes
    return DeviceBatchedBufferStager(members)


# kind -> (the request's stager, does the caller wait, has the device room?)
KINDS = {
    "as_it_lies_for_a_caller_that_waits": (lambda: _leaf_stager(jnp.asarray(_values(LEAF, 60)), "0/k"), True, True),
    "as_it_lies_for_want_of_room": (lambda: _leaf_stager(jnp.asarray(_values(LEAF, 61)), "0/k"), False, False),
    "owned": (lambda: _leaf_stager(jnp.asarray(_values(LEAF, 62)), "0/k"), False, True),
    "turned": (lambda: _leaf_stager(_swapped(_values((168, 125), 63)), "0/k"), True, True),
    "slab": (_slab_of_device_leaves, True, True),
    "numpy": (lambda: _leaf_stager(_values(LEAF, 64), "0/k"), True, True),
}
UNCHARGED = {"as_it_lies_for_a_caller_that_waits", "as_it_lies_for_want_of_room"}


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_only_a_leaf_that_crosses_as_it_lies_is_dispatched_with_the_budget_at_zero(
    tmp_path, monkeypatch, on_an_accelerator, kind
):
    """A numpy leaf as large as the window is staged and its write does
    not end: the budget stands at zero. The request behind it is staged
    all the same where it crosses as it lies (its host value stays with
    the caller's array whenever the write ends), and waits for that
    write's end where its staged buffer is tpusnap's own: an owned
    copy's host value, a leaf that will be turned, a slab, a numpy leaf.
    The budget's high-water mark is the window in every case."""
    make, waits, room = KINDS[kind]
    if not room:
        monkeypatch.setattr(array_preparer, "_device_free_bytes", lambda device: 0)
    first = _values((128, 256), 1)  # 128 KiB: the window
    window = first.nbytes
    under_test = make()
    reqs = [WriteReq(path="0/first", buffer_stager=_leaf_stager(first, "0/first")),
            WriteReq(path="0/k", buffer_stager=under_test)]
    assert reqs[0].buffer_stager.get_staging_cost_bytes() == window  # copy-on-write: no clone's second copy
    gate = asyncio.Event()

    class Held(FSStoragePlugin):
        async def write(self, write_io):
            await gate.wait()
            await super().write(write_io)

    rec = telemetry.TakeTelemetry(rank=0, enabled=False)
    seen = {}
    owned_before = telemetry.counter_value("dtoh.owned_leaves")

    async def go():
        sched = _WriteScheduler(reqs, Held(str(tmp_path)), 1 << 30, rank=0, pipelined_staging=True, tele=rec)
        assert sched.memory_budget_bytes == window and sched.host_budget_bytes == 1 << 30
        pending = PendingIOWork(sched)
        with pending.caller_waits() if waits else contextlib.nullcontext():
            await sched.run_blocked_window()  # the numpy leaf is held in the window
            # The window's one request is staged and holds the whole budget;
            # the loop has looked at the request behind it once already.
            assert sched.budget == 0 and len(sched.pipelines) == (0 if kind in UNCHARGED else 1)
            drain = asyncio.ensure_future(sched.drain())
            for _ in range(500):
                await asyncio.sleep(0.002)
                if sched.staging_complete or (sched.io_tasks and sched._staging_budget_starved()):
                    break
            seen.update(staged=sched.staging_complete, starved=sched._staging_budget_starved(),
                        budget=sched.budget, uncharged=under_test.stages_callers_host_value()
                        if isinstance(under_test, ArrayBufferStager) else False)
            gate.set()
            await drain
        assert sched.budget == window

    with override_async_stage_window_bytes(first.nbytes), override_stage_threads(1):
        asyncio.run(go())
    summary = rec.summary()
    uncharged = kind in UNCHARGED
    assert seen == {"staged": uncharged, "starved": not uncharged, "budget": 0, "uncharged": uncharged}
    assert summary["gauges"]["scheduler.budget_used_bytes"] == window
    nbytes = sum(n for _, n, _ in under_test.members) if kind == "slab" else under_test.arr.nbytes
    assert summary["counters"].get("scheduler.uncharged_bytes", 0) == (nbytes if uncharged else 0)
    assert telemetry.counter_value("dtoh.owned_leaves") - owned_before == (kind == "owned")
    want = (b"".join(np.asarray(m.arr).tobytes() for _, _, m in under_test.members) if kind == "slab"
            else np.ascontiguousarray(np.asarray(under_test.arr)).tobytes())
    assert (tmp_path / "0" / "k").read_bytes() == want
    assert (tmp_path / "0" / "first").read_bytes() == first.tobytes()


def test_a_turn_that_the_layout_did_not_foretell_is_charged_when_it_happens(on_an_accelerator, monkeypatch):
    """A leaf that crossed as it lies and reached the host in another
    order than C is staged from a pooled buffer of tpusnap's own: the
    stager says so once staging has turned it, and the scheduler settles
    the charge at staging's end."""
    host = np.asfortranarray(_values((168, 125), 65))
    arr = jnp.asarray(host)
    st = _leaf_stager(arr, "0/t")
    st.beside_steps = False
    assert st.start_dtoh() == arr.nbytes and st.stages_callers_host_value()
    monkeypatch.setattr(ArrayBufferStager, "host_array", lambda self: host)  # lands turned
    staged = st._stage_blocking()
    assert not st.stages_callers_host_value()
    assert bytes(memoryview(staged)) == np.ascontiguousarray(host).tobytes()


# ------------------------------------------- (3) through the take, with donation


def _donating_step():
    return jax.jit(lambda tree: jax.tree.map(lambda x: x * 2 + 1, tree), donate_argnums=0)


@pytest.mark.parametrize("threads", [1, 2], ids=["one_staging_thread", "two_staging_threads"])
def test_a_caller_that_donates_after_wait_staged_commits_what_one_that_does_not_commits(
    tmp_path, monkeypatch, on_an_accelerator, transfers_land_copies, threads
):
    """``async_take``, ``wait_staged()`` with every write still held back,
    a jitted step that donates (and so deletes) the whole state, and only
    then the writes: blobs and manifest are byte for byte those of a plain
    ``take`` of the same values on this backend with nothing patched (the
    parent's path), the counters say that the leaves the caller waited for
    crossed as they lay, uncharged, and were started past the stepping
    depth, and ``trace`` prints them."""
    import tpusnap.scheduler as scheduler_mod

    values = {f"w{i}": _values(LEAF, 80 + i) for i in range(6)}
    values["b"] = _values((64,), 90)
    with override_batching_disabled(True):
        with monkeypatch.context() as plain:
            plain.undo()  # nothing patched: a CPU backend's own path
            Snapshot.take(str(tmp_path / "off"), {"m": PytreeState({k: jnp.asarray(v) for k, v in values.items()})})
        state = {k: jnp.asarray(v) for k, v in values.items()}
        monkeypatch.setattr(scheduler_mod, "_DTOH_LOOKAHEAD_BYTES", values["w0"].nbytes)
        writes, staging = _HeldWrites(monkeypatch), _StagingHeldUntilTheCallerWaits(monkeypatch)
        with override_stage_threads(threads):
            pending = Snapshot.async_take(str(tmp_path / "on"), {"m": PytreeState(state)})
            staging.watch(pending)
            assert pending.wait_staged(timeout=30) and pending.staged()
            assert not pending._pending_io_work.drained()
            stepped = _donating_step()(state)
            jax.block_until_ready(stepped)
            assert all(x.is_deleted() for x in state.values())
            writes.open()
            pending.wait()
    assert _files(tmp_path / "on") == _files(tmp_path / "off")
    assert len(_files(tmp_path / "on")[0]) == len(values)
    for k, v in values.items():  # and the step ran on what it was given
        assert np.array_equal(np.asarray(stepped[k]), v * 2 + 1)
    counters = telemetry.LAST_TAKE_SUMMARY["counters"]
    leaf, small = values["w0"].nbytes, values["b"].nbytes
    # What the blocked window dispatched (a request a staging thread) and
    # its lookahead were started for a caller that might step: owned
    # copies, charged. The caller's arrival started all the rest as it
    # lies, past the stepping depth (a leaf's bytes here), uncharged.
    owned = 1 + threads
    assert counters["dtoh.owned_leaves"] == owned and counters["dtoh.owned_bytes"] == owned * leaf
    assert counters["dtoh.owned_waived"] == 6 - owned
    assert counters["scheduler.uncharged_bytes"] == (6 - owned) * leaf + small
    assert counters["dtoh.deep_starts"] == 7 - owned
    assert counters["dtoh.deep_bytes"] == (6 - owned) * leaf + small
    assert telemetry.LAST_TAKE_SUMMARY["gauges"]["scheduler.budget_used_bytes"] == owned * leaf
    from tpusnap.__main__ import main as cli

    out = io.StringIO()
    with redirect_stdout(out):
        assert cli(["trace", str(tmp_path / "on")]) == 0
    for name in ("scheduler.uncharged_bytes", "dtoh.deep_starts", "dtoh.deep_bytes"):
        assert name in out.getvalue(), name


# ------------------------------------------------- (4) the budget's waits, as spans


class _Spans(telemetry.MetricsSink):
    def __init__(self):
        self.spans = []

    def on_span_record(self, record):
        self.spans.append(record)


@pytest.mark.parametrize("starved", [False, True], ids=["never_waited", "waited_for_a_write"])
def test_a_takes_staging_ends_with_a_budget_wait_span_empty_where_none_was_open(tmp_path, starved):
    """s:``budget_wait`` is one span an episode in which the head request
    waited for its charge. A take's staging ends with one more, from the
    episode still open to staging's end, which is empty where none is
    open: a take that never waited then reads 0 under
    ``budget_wait_ms`` (``perf/layer_metrics``), where no span at all
    reads as "not recorded"."""
    from tpusnap import metrics_sink

    state = _numpy(4)  # charged: written from the live bytes under copy-on-write
    window = (2 if starved else 8) * state["h0"].nbytes
    with override_batching_disabled(True), override_async_stage_window_bytes(window), metrics_sink(_Spans()) as sink:
        Snapshot.async_take(str(tmp_path / "snap"), {"m": PytreeState(state)}).wait()
    waits = [s for s in sink.spans if s.name == "budget_wait"]
    assert all(s.kind == telemetry.WAIT for s in waits)
    if starved:
        assert len(waits) >= 2 and sum(s.end - s.start for s in waits) > 0
    else:
        assert len(waits) == 1 and waits[0].end - waits[0].start == pytest.approx(0.0, abs=1e-9)
