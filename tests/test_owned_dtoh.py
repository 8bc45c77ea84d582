"""A device leaf crosses to the host from a copy on the chip that tpusnap
owns (PR 51): ``ArrayBufferStager.start_dtoh()`` has the runtime copy a
large accelerator leaf on its own device (``jax.device_put(...,
may_alias=False)``: no compiled program, the leaf's shape, type and layout),
starts that copy's transfer, and lets the copy go once its bytes are seen on
the host. The caller's buffer, which its next step reads, is never the source.

Only where the caller's steps may run beside the transfer (a pipelined
``async_take`` whose caller is not inside ``wait_staged()``) and the device
has the room free by its own count; elsewhere the leaf crosses as it lies.

A CPU backend's array never qualifies (its ``np.asarray`` is a view) and a
CPU backend reports no memory, so these tests patch the one function that
says "this array lies on one accelerator" to true and the one that reads
the device's free bytes, and hold the rest of the rule, the bytes and the
counts as they are. Counts and bytes on the CPU, never a time."""

import asyncio
import gc
import json
import os
import weakref

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest
from jax.experimental.layout import Format, Layout

from tpusnap import PytreeState, Snapshot, _native, metrics_sink, telemetry
from tpusnap import compress as compress_mod
from tpusnap.batcher import BatchedBufferStager, DeviceBatchedBufferStager
from tpusnap.io_preparers import array as array_preparer
from tpusnap.io_preparers.array import ArrayBufferStager, DonatedBeforeStagedError
from tpusnap.io_types import WriteReq
from tpusnap.knobs import override_batching_disabled, override_compress
from tpusnap.manifest import TensorEntry
from tpusnap.scheduler import _WriteScheduler
from tpusnap.serialization import RELAYOUT_MIN_BYTES, dtype_to_string

FLOOR = 1 << 16  # the rule's floor in these tests; one test keeps the real one
COUNTERS = (
    "owned_leaves", "owned_bytes", "owned_fallbacks", "owned_waived", "enqueued_bytes", "cold_fetches"
)


def _accelerator(monkeypatch, free=1 << 40):
    """Every jax.Array counts as lying on one accelerator whose memory has
    ``free`` bytes that nothing has reached."""
    monkeypatch.setattr(array_preparer, "_lies_on_one_accelerator", lambda arr: True)
    monkeypatch.setattr(array_preparer, "_device_free_bytes", lambda device: free)


@pytest.fixture()
def on_an_accelerator(monkeypatch):
    """That accelerator, and the floor small enough for a test's leaves."""
    _accelerator(monkeypatch)
    monkeypatch.setattr(array_preparer, "RELAYOUT_MIN_BYTES", FLOOR)


def _counts():
    return {k: telemetry.counter_value(f"dtoh.{k}") for k in COUNTERS}


def _grown(before):
    return {k: v - before[k] for k, v in _counts().items()}


def _only(**grown):
    return {**dict.fromkeys(COUNTERS, 0), **grown}


def _values(shape, dtype, seed):
    rng = np.random.default_rng(seed)
    n = int(np.prod(shape)) if shape else 1
    raw = rng.integers(0, 256, n * np.dtype(dtype).itemsize, dtype=np.uint8)
    return raw.view(dtype).reshape(shape)


def _swapped(values):
    """A device array whose minor dimension is its first: how a TPU lays
    a leaf out whose last dimension is no multiple of 128."""
    dev = jax.devices()[0]
    fmt = Format(Layout(major_to_minor=(0, 1)), jax.sharding.SingleDeviceSharding(dev))
    arr = jax.device_put(values, fmt)
    assert arr.format.layout.major_to_minor == (0, 1)
    return arr


def _entry(arr, location) -> TensorEntry:
    return TensorEntry(
        location=location,
        serializer="buffer_protocol",
        dtype=dtype_to_string(arr.dtype),
        shape=list(arr.shape),
        replicated=False,
    )


def _stager(arr, location="0/w", **kwargs) -> ArrayBufferStager:
    return ArrayBufferStager(arr, entry=_entry(arr, location), **kwargs)


# What crosses from an owned copy, and what is left alone. Sizes against
# FLOOR (64 KiB). Any element type, any rank, any layout.
OWNED = {
    "f32_rank1": lambda: jnp.asarray(_values((32768,), np.float32, 1)),
    "f32_rank2": lambda: jnp.asarray(_values((96, 256), np.float32, 2)),
    "f32_rank3": lambda: jnp.asarray(_values((3, 64, 128), np.float32, 3)),
    "f32_minor_off_128": lambda: _swapped(_values((168, 125), np.float32, 4)),
    "bf16_rank2": lambda: jnp.asarray(_values((256, 256), ml_dtypes.bfloat16, 5)),
    "int8_rank2": lambda: jnp.asarray(_values((256, 512), np.int8, 6)),
    "int8_rank3": lambda: jnp.asarray(_values((4, 100, 200), np.int8, 7)),
}
ALONE = {
    "rank0": lambda: jnp.asarray(np.float32(3.5)),
    "under_the_floor": lambda: jnp.asarray(_values((63, 256), np.float32, 8)),
    "numpy": lambda: _values((96, 256), np.float32, 9),
}


def _state():
    return {k: make() for k, make in {**OWNED, **ALONE}.items()}


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


def _take(how, path, state):
    app = {"train": PytreeState(state)}
    before = _counts()
    if how == "take":
        Snapshot.take(path, app)
    else:
        Snapshot.async_take(path, app).wait()
    return _grown(before)


# ------------------------------------------------------ through the take


@pytest.mark.parametrize("how", ["take", "async_take"])
def test_a_take_through_owned_copies_writes_the_bytes_a_take_without_writes(
    tmp_path, monkeypatch, how
):
    """Every blob and the manifest byte for byte, the restore equal, and
    the counters say which leaves crossed from a copy of tpusnap's own:
    f32, bf16 and int8, rank 1 to 3, a minor dimension off 128, and none
    of the small or host leaves. Under ``take`` the caller stands in the
    take until the state is staged, so no step could run beside a copy:
    the same leaves cross as they lie, and are counted as waived."""
    state = _state()
    nbytes = sum(x.nbytes for x in jax.tree.leaves(state) if isinstance(x, jax.Array))
    owned_bytes = sum(state[k].nbytes for k in OWNED)
    with override_batching_disabled(True):
        monkeypatch.setattr(array_preparer, "RELAYOUT_MIN_BYTES", FLOOR)
        off = _take(how, str(tmp_path / "off"), state)
        relayouts_off = telemetry.LAST_TAKE_SUMMARY["counters"].get("stage.relayouts")
        _accelerator(monkeypatch)
        on = _take(how, str(tmp_path / "on"), state)
    assert off == _only(enqueued_bytes=nbytes)
    counters = telemetry.LAST_TAKE_SUMMARY["counters"]
    if how == "take":
        assert on == _only(owned_waived=len(OWNED), enqueued_bytes=nbytes)
        assert counters["dtoh.owned_waived"] == len(OWNED) and "dtoh.owned_bytes" not in counters
    else:
        assert on == _only(owned_leaves=len(OWNED), owned_bytes=owned_bytes, enqueued_bytes=nbytes)
        assert counters["dtoh.owned_leaves"] == len(OWNED)
        assert counters["dtoh.owned_bytes"] == owned_bytes and "dtoh.owned_waived" not in counters
    assert "dtoh.owned_fallbacks" not in counters
    # The host turns what it turned (on this backend nothing: a swapped
    # leaf's ``np.asarray`` is C-ordered here; the copy keeps the layout).
    assert counters.get("stage.relayouts") == relayouts_off
    blobs_off, manifest_off = _files(tmp_path / "off")
    blobs_on, manifest_on = _files(tmp_path / "on")
    assert len(blobs_on) == len(state) and blobs_on == blobs_off
    assert manifest_on == manifest_off
    target = {"train": PytreeState(jax.tree.map(lambda x: np.zeros_like(np.asarray(x)), state))}
    Snapshot(str(tmp_path / "on")).restore(target)
    for k, want in state.items():
        got = np.asarray(target["train"].tree[k])
        assert got.dtype == want.dtype and got.tobytes() == np.asarray(want).tobytes(), k


def test_the_real_floor_is_the_host_relayouts_own(monkeypatch):
    """With the constant as it stands: a leaf of exactly that size is
    copied and one a row shorter is not."""
    _accelerator(monkeypatch)
    rows = RELAYOUT_MIN_BYTES // 4096
    at, under = jnp.ones((rows, 1024), jnp.float32), jnp.ones((rows - 1, 1024), jnp.float32)
    before = _counts()
    assert _stager(at).start_dtoh() == at.nbytes and _stager(under).start_dtoh() == under.nbytes
    assert _grown(before) == _only(
        owned_leaves=1, owned_bytes=at.nbytes, enqueued_bytes=at.nbytes + under.nbytes
    )


def test_the_copy_has_a_span_and_the_transfer_starts_behind_the_copy(tmp_path, on_an_accelerator):
    """s:``dtoh.own_copy`` (kind work, the leaf's bytes) once an owned
    leaf; the leaf's ``dtoh.transfer`` starts where ``copy_to_host_async``
    is called, as a leaf's that crosses as it lies: inside that span,
    behind the making of the copy."""

    class Sink(telemetry.MetricsSink):
        def __init__(self):
            self.spans = []

        def on_span_record(self, record):
            self.spans.append(record)

    state = {"w": OWNED["f32_rank3"](), "b": ALONE["under_the_floor"]()}
    with override_batching_disabled(True), metrics_sink(Sink()) as sink:
        Snapshot.async_take(str(tmp_path / "snap"), {"train": PytreeState(state)}).wait()
    (copy,) = [s for s in sink.spans if s.name == "dtoh.own_copy"]
    assert copy.kind == telemetry.WORK and copy.attrs["bytes"] == state["w"].nbytes
    transfers = {s.attrs["bytes"]: s for s in sink.spans if s.name == "dtoh.transfer"}
    assert set(transfers) == {state["w"].nbytes, state["b"].nbytes}
    started = transfers[state["w"].nbytes].start
    assert copy.start < started <= copy.end + 1e-9


def test_the_copy_is_the_runtimes_and_compiles_nothing():
    """``_own_copy`` gives another buffer with the same elements in the
    same layout, and no event of a lowering, a compilation or a cache
    lookup fires for it, whatever the shape (a shape no test has used)."""
    from jax import monitoring

    fired = []
    listen = lambda name, *a, **kw: fired.append(name)  # noqa: E731
    monitoring.register_event_duration_secs_listener(listen)
    monitoring.register_event_listener(listen)
    try:
        for arr in (
            jnp.asarray(_values((7, 13, 31), np.float32, 20)),
            _swapped(_values((77, 51), np.float32, 21)),
            jnp.asarray(_values((19, 23), ml_dtypes.bfloat16, 22)),
        ):
            want = np.asarray(arr)
            del fired[:]
            copy = array_preparer._own_copy(arr)
            assert [n for n in fired if "compil" in n or "mlir" in n or "trace" in n] == []
            assert copy is not arr and copy.unsafe_buffer_pointer() != arr.unsafe_buffer_pointer()
            assert copy.format == arr.format and copy.dtype == arr.dtype and copy.shape == arr.shape
            got = np.asarray(copy)
            assert got.strides == want.strides and got.tobytes() == want.tobytes()
    finally:
        monitoring.unregister_event_duration_listener(listen)
        monitoring.unregister_event_listener(listen)


# ------------------------------------------------------------- the rule


@pytest.mark.parametrize("leaf", sorted(OWNED))
def test_an_owned_leaf_is_staged_as_its_own_bytes_and_the_copy_is_let_go(on_an_accelerator, leaf):
    """The staged bytes are the leaf's in C order, the copy is counted
    once, the source is never asked for a transfer, and the copy on the
    device is gone once the leaf is staged."""
    arr = OWNED[leaf]()
    st = _stager(arr)
    before = _counts()
    assert st.start_dtoh() == arr.nbytes == st.start_dtoh()
    owned = weakref.ref(st._owned)
    assert owned() is not arr and owned().shape == arr.shape and owned().dtype == arr.dtype
    assert owned().unsafe_buffer_pointer() != arr.unsafe_buffer_pointer()
    staged = st._stage_blocking()
    assert bytes(memoryview(staged)) == np.ascontiguousarray(np.asarray(arr)).tobytes()
    # (On this backend the staged bytes are a view of the copy's own
    # buffer; an accelerator's are a host copy that holds nothing.)
    del staged
    gc.collect()
    assert st._owned is None and owned() is None
    assert _grown(before) == _only(
        owned_leaves=1, owned_bytes=arr.nbytes, enqueued_bytes=arr.nbytes
    )


def _behind_a_transform():
    arr = OWNED["f32_rank2"]()
    return _stager(arr, array_prepare_func=lambda a, tracing: a * 2)


def _slab_member(slab):
    arr = OWNED["f32_rank2"]()
    member = _stager(arr)
    slab([(0, arr.nbytes, member)])
    return member


LEFT_ALONE = {
    **{k: (lambda make=make: _stager(make())) for k, make in ALONE.items()},
    "behind_a_transform": _behind_a_transform,
    "host_slab_member": lambda: _slab_member(BatchedBufferStager),
    "device_slab_member": lambda: _slab_member(DeviceBatchedBufferStager),
}


@pytest.mark.parametrize("case", sorted(LEFT_ALONE))
def test_what_the_rule_leaves_alone_crosses_as_before(on_an_accelerator, case):
    st = LEFT_ALONE[case]()
    before = _counts()
    started = st.start_dtoh()
    assert st._owned is None
    grown = _grown(before)
    assert grown["owned_leaves"] == grown["owned_bytes"] == grown["owned_fallbacks"] == 0
    device = isinstance(st.arr, jax.Array) and st.array_prepare_func is None
    assert started == grown["enqueued_bytes"] == (st.arr.nbytes if device else 0)
    want = st.arr if st.array_prepare_func is None else st.arr * 2
    assert bytes(memoryview(st._stage_blocking())) == np.asarray(want).tobytes()


def test_a_cpu_backends_array_never_qualifies():
    """Unpatched: ``np.asarray`` of a CPU backend's array is a view, and
    the rule reads that off the backend's own probe."""
    arr = jnp.ones((RELAYOUT_MIN_BYTES // 4096, 1024), jnp.float32)
    assert not array_preparer._lies_on_one_accelerator(arr)
    assert not array_preparer._lies_on_one_accelerator(np.ones((4, 4), np.float32))
    st = _stager(arr)
    before = _counts()
    assert st.start_dtoh() == arr.nbytes and st._owned is None
    assert _grown(before) == _only(enqueued_bytes=arr.nbytes)


# ------------------------------------------------------ when the chip is full


def _out_of_memory(*_):
    raise jax.errors.JaxRuntimeError(
        "RESOURCE_EXHAUSTED: Error allocating device buffer: Attempting to allocate 256.00M."
    )


class _FetchFails:
    """A copy whose transfer was started and whose fetch fails."""

    def __init__(self, error):
        self.error = error

    def copy_to_host_async(self):
        pass

    def __array__(self, *args, **kwargs):
        self.error()


def _never_made(*_):
    raise AssertionError("a copy was made where the device's count had no room for it")


@pytest.mark.parametrize("where", ["count", "copy", "fetch"])
def test_a_copy_that_finds_no_room_falls_back_and_commits_the_same_bytes(
    tmp_path, monkeypatch, where
):
    """By the device's own count before the copy is made (the caller's
    next step needs that room), by the runtime's refusal of the copy, or
    by its refusal of the fetch."""
    state = _state()
    with override_batching_disabled(True):
        monkeypatch.setattr(array_preparer, "RELAYOUT_MIN_BYTES", FLOOR)
        _take("async_take", str(tmp_path / "off"), state)
        _accelerator(monkeypatch, free=FLOOR - 1 if where == "count" else 1 << 40)
        copy = {"count": _never_made, "copy": _out_of_memory}.get(where, lambda a: _FetchFails(_out_of_memory))
        monkeypatch.setattr(array_preparer, "_own_copy", copy)
        grown = _take("async_take", str(tmp_path / "on"), state)
    assert grown["owned_fallbacks"] == len(OWNED) and grown["cold_fetches"] == 0
    # A copy that was never made started no transfer; one whose fetch
    # failed had, and the leaf's second crossing is counted beside it.
    assert grown["owned_leaves"] == (len(OWNED) if where == "fetch" else 0)
    nbytes = sum(x.nbytes for x in jax.tree.leaves(state) if isinstance(x, jax.Array))
    again = sum(state[k].nbytes for k in OWNED) if where == "fetch" else 0
    assert grown["enqueued_bytes"] == nbytes + again
    assert _files(tmp_path / "on") == _files(tmp_path / "off")


def _internal(*_):
    raise jax.errors.JaxRuntimeError("INTERNAL: the chip said no")


def _value_error(*_):
    raise ValueError("not the runtime's")


@pytest.mark.parametrize("where", ["copy", "fetch"])
@pytest.mark.parametrize("error", [_internal, _value_error])
def test_any_other_error_of_the_copy_propagates(tmp_path, monkeypatch, on_an_accelerator, where, error):
    copy = error if where == "copy" else lambda a: _FetchFails(error)
    monkeypatch.setattr(array_preparer, "_own_copy", copy)
    before = _counts()
    expected = ValueError if error is _value_error else jax.errors.JaxRuntimeError
    with pytest.raises(expected, match="not the runtime's|the chip said no"):
        Snapshot.async_take(str(tmp_path / "snap"), {"train": PytreeState({"w": OWNED["f32_rank2"]()})}).wait()
    assert _grown(before)["owned_fallbacks"] == 0



class _Device:
    """A device that reports ``stats`` as its memory's."""

    def __init__(self, stats):
        self.stats = stats

    def memory_stats(self):
        return self.stats


@pytest.mark.parametrize(
    "stats,free",
    [
        ({"bytes_limit": 16 << 30, "peak_bytes_in_use": 10 << 30, "bytes_in_use": 5 << 30}, 5 << 30),
        ({"bytes_limit": 16 << 30, "peak_bytes_in_use": 31 << 29}, -(1 << 29)),
        ({"bytes_in_use": 5 << 30}, None),
        (None, None),
    ],
    ids=["limit_less_peak_less_a_sixteenth", "within_the_share_kept_clear", "no_limit_reported", "no_stats"],
)
def test_a_devices_free_bytes_are_its_limit_less_its_peak_less_the_share_kept_clear(stats, free):
    """Read off the allocator's peak, not its present use: the caller's
    next step will want what its largest step wanted."""
    assert array_preparer._device_free_bytes(_Device(stats)) == free


def test_a_device_that_reports_no_memory_has_no_room(monkeypatch):
    """Unpatched free bytes (a CPU backend reports none): the leaf
    crosses as it lies and is counted as a fallback, not copied on faith."""
    monkeypatch.setattr(array_preparer, "_lies_on_one_accelerator", lambda arr: True)
    monkeypatch.setattr(array_preparer, "RELAYOUT_MIN_BYTES", FLOOR)
    monkeypatch.setattr(array_preparer, "_own_copy", _never_made)
    arr = OWNED["f32_rank2"]()
    st = _stager(arr)
    before = _counts()
    assert st.start_dtoh() == arr.nbytes and st._owned is None
    assert _grown(before) == _only(owned_fallbacks=1, enqueued_bytes=arr.nbytes)


def test_the_copies_alive_count_against_the_room_until_they_are_let_go(monkeypatch):
    """Room for two leaves and a half: the third leaf started while two
    copies live crosses as it lies; once a copy's bytes are staged and
    the copy is gone, the next leaf is copied again. A leaf another
    stager holds counts too (the count is the device's, not a take's)."""
    leaf = 96 * 256 * 4
    _accelerator(monkeypatch, free=2 * leaf + leaf // 2)
    monkeypatch.setattr(array_preparer, "RELAYOUT_MIN_BYTES", FLOOR)
    device = jax.devices()[0]
    gc.collect()
    live = array_preparer._owned_live.get(device, 0)
    stagers = [_stager(OWNED["f32_rank2"](), f"0/w{i}") for i in range(4)]
    before = _counts()
    for st in stagers[:3]:
        assert st.start_dtoh() == leaf
    assert [st._owned is not None for st in stagers[:3]] == [True, True, False]
    assert array_preparer._owned_live[device] == live + 2 * leaf
    assert _grown(before) == _only(
        owned_leaves=2, owned_bytes=2 * leaf, owned_fallbacks=1, enqueued_bytes=3 * leaf
    )
    stagers[0]._stage_blocking()  # staged, and the staged bytes dropped
    gc.collect()
    assert array_preparer._owned_live[device] == live + leaf
    assert stagers[3].start_dtoh() == leaf and stagers[3]._owned is not None
    for st in stagers[1:]:
        assert bytes(memoryview(st._stage_blocking())) == np.asarray(st.arr).tobytes()
    del st
    stagers.clear()
    gc.collect()
    assert array_preparer._owned_live[device] == live


# ------------------------------------------- where no step runs beside the copy


def _scheduler(leaves, budget=1 << 30, **modes):
    reqs = [
        WriteReq(path=f"0/w{i}", buffer_stager=_stager(OWNED["f32_rank2"](), f"0/w{i}"))
        for i in range(leaves)
    ]
    return _WriteScheduler(reqs, None, budget, rank=0, **modes), [r.buffer_stager for r in reqs]


def _shut(sched):
    sched.executor.shutdown()
    sched.hash_executor.shutdown()


@pytest.mark.parametrize(
    "modes,may",
    [({"pipelined_staging": True}, True), ({}, False), ({"prioritize_staging": True}, False)],
    ids=["pipelined_async_take", "take", "async_take_that_stages_before_it_returns"],
)
def test_steps_may_run_only_beside_a_take_that_returns_before_it_has_staged(on_an_accelerator, modes, may):
    """What the scheduler observes: a pipelined async take hands control
    back with the state still to cross; every other take's caller stands
    in the take until the state is staged. The copies follow it."""
    sched, stagers = _scheduler(2, **modes)
    before = _counts()
    try:
        assert sched._steps_may_run() is may
        sched.pipelines.popleft()
        sched._start_dtoh_ahead()
    finally:
        _shut(sched)
    nbytes = sum(st.arr.nbytes for st in stagers)
    assert [st._owned is not None for st in stagers] == [may, may]
    assert _grown(before) == (
        _only(owned_leaves=2, owned_bytes=nbytes, enqueued_bytes=nbytes)
        if may
        else _only(owned_waived=2, enqueued_bytes=nbytes)
    )


@pytest.mark.parametrize(
    "budget,owned",
    [(96 * 256 * 4, [True, True, False, False, True, True]), (1 << 30, [True, True] + [False] * 4)],
    ids=["a_host_budget_of_one_leaf", "a_host_budget_over_the_state"],
)
def test_a_caller_inside_wait_staged_gets_no_copy_made_for_its_sake(on_an_accelerator, monkeypatch, budget, owned):
    """A donating trainer: the leaves started before it came back to wait
    cross from owned copies, those started while it stands in
    ``wait_staged()`` cross as they lie and are counted as waived, and
    those started after it left are copied again. Two waiters count twice.
    While it stands there no step can run, so the copies are started four
    times as far ahead, within the host-memory budget (PR 52): with room
    for the state, all that are left, at the first dispatch that finds it
    waiting."""
    from tpusnap.scheduler import PendingIOWork

    monkeypatch.setattr("tpusnap.scheduler._DTOH_LOOKAHEAD_BYTES", 0)
    sched, stagers = _scheduler(6, budget=budget, pipelined_staging=True)
    pending = PendingIOWork(sched)
    before = _counts()

    def dispatch():
        sched.pipelines.popleft()
        sched._start_dtoh_ahead()

    try:
        dispatch()  # the request dispatched and the one after it
        with pending.caller_waits():
            assert sched.callers_waiting == 1 and not sched._steps_may_run()
            with pending.caller_waits():
                assert sched.callers_waiting == 2
            dispatch()
            with pytest.raises(KeyError), pending.caller_waits():
                raise KeyError("the wait died")
            dispatch()
        assert sched.callers_waiting == 0 and sched._steps_may_run()
        dispatch()
        dispatch()
    finally:
        _shut(sched)
    assert [st._owned is not None for st in stagers] == owned
    leaf = stagers[0].arr.nbytes
    assert _grown(before) == _only(
        owned_leaves=sum(owned), owned_bytes=sum(owned) * leaf, owned_waived=6 - sum(owned),
        enqueued_bytes=6 * leaf,
    )
    # What crossed as it lies stages the host value kept on the caller's
    # array, and says so to the budget; an owned copy's host value is tpusnap's.
    assert [st.stages_callers_host_value() for st in stagers] == [not o for o in owned]


def test_wait_staged_tells_the_scheduler_that_its_caller_waits(tmp_path, monkeypatch):
    """``PendingSnapshot.wait_staged()`` stands inside ``caller_waits()``
    for the length of its wait, with or without a timeout, and leaves it."""
    from tpusnap.scheduler import PendingIOWork

    seen = []

    def watched(name):
        plain = getattr(PendingIOWork, name)

        def wait(self, timeout=None):
            seen.append(self.scheduler.callers_waiting)
            return plain(self, timeout)

        monkeypatch.setattr(PendingIOWork, name, wait)

    watched("wait_staged")
    watched("wait_drained")  # what it waits for under TPUSNAP_ASYNC_COW
    pending = Snapshot.async_take(str(tmp_path / "snap"), {"train": PytreeState({"w": jnp.ones((64, 64))})})
    assert pending.wait_staged() and pending.wait_staged(timeout=5.0)
    assert seen and set(seen) == {1}
    assert pending._pending_io_work.scheduler.callers_waiting == 0
    pending.wait()


# --------------------------------------------------- the sampler's one copy


@pytest.mark.skipif(
    not _native.compression_available(), reason="native codec unavailable (no toolchain)"
)
def test_the_codec_sampler_and_staging_share_one_copy_and_one_fetch(monkeypatch, on_an_accelerator):
    """The sampler starts its source's owned copy and reads it through the
    stager; the scheduler's first dispatch finds it under way, and staging
    fetches the same copy: made once, counted once, no cold fetch, the
    lookahead's gauge as it was."""
    monkeypatch.setattr(compress_mod, "AUTO_MIN_TAKE_BYTES", 1 << 18)
    monkeypatch.setattr(compress_mod, "pipe_ceiling_key", lambda storage: "X51")
    compress_mod.note_pipe_ceiling("X51", 1e6)  # a pipe no codec outruns: nothing is compressed
    copies = []
    plain = array_preparer._own_copy
    monkeypatch.setattr(array_preparer, "_own_copy", lambda a: copies.append(a.shape) or plain(a))
    values = {k: _values(s, np.float32, i) for i, (k, s) in enumerate(
        {"a": (256, 1024), "b": (1024, 1024), "c": (1024, 1024)}.items())}
    reqs = [
        WriteReq(path=f"0/{k}", buffer_stager=_stager(jnp.asarray(v), f"0/{k}"))
        for k, v in values.items()
    ]
    before = _counts()
    with override_compress(mode="auto", min_blob_bytes=65536):
        decision = compress_mod.apply_take_policy(reqs, None, None, rec=None)
    assert decision.sample_bytes == values["b"].nbytes and not decision.compress
    source = reqs[1].buffer_stager
    assert source._owned is not None and [r.buffer_stager._owned for r in (reqs[0], reqs[2])] == [None, None]
    # One fetch: JAX keeps the host value with the copy it was fetched from.
    assert np.shares_memory(source.host_array(), source.host_array())
    assert _grown(before) == _only(
        owned_leaves=1, owned_bytes=values["b"].nbytes, enqueued_bytes=values["b"].nbytes
    )
    total = sum(v.nbytes for v in values.values())
    sched = _WriteScheduler(reqs, None, 1 << 30, rank=0, pipelined_staging=True)
    try:
        assert sched.pipelines[0].write_req.buffer_stager is source
        sched.pipelines.popleft()
        sched._start_dtoh_ahead()
        assert sched.dtoh_unfetched_bytes == total
    finally:
        sched.executor.shutdown()
        sched.hash_executor.shutdown()
    for k, wr in zip(values, reqs):
        assert bytes(memoryview(wr.buffer_stager._stage_blocking())) == values[k].tobytes()
    # The source's first, by the sampler; then the queue's order, largest first.
    assert copies == [values[k].shape for k in "bca"]
    assert _grown(before) == _only(owned_leaves=3, owned_bytes=total, enqueued_bytes=total)
    assert all(r.buffer_stager._owned is None for r in reqs)


# ------------------------------------------------------------ a donated leaf


@pytest.mark.parametrize("deleted", ["before_the_start", "after_the_start"])
def test_a_source_deleted_before_staging_still_fails_by_the_leafs_name(on_an_accelerator, deleted):
    """Once the copy is made the bytes are tpusnap's own, but the contract
    is unchanged: a source deleted before its leaf is staged fails the
    take by the leaf's name."""
    arr = OWNED["f32_rank3"]()
    st = _stager(arr, "0/train/w2", is_async_snapshot=True)
    if deleted == "after_the_start":
        assert st.start_dtoh() == arr.nbytes and st._owned is not None
    arr.delete()  # what donating it to a jitted step does
    if deleted == "before_the_start":
        assert st.start_dtoh() == 0 and st._owned is None
    with pytest.raises(DonatedBeforeStagedError, match=r"0/train/w2.*wait_staged\(\)"):
        asyncio.run(st.stage_buffer())


def test_a_source_deleted_under_the_copy_fails_by_the_leafs_name(monkeypatch, on_an_accelerator):
    """The step donates between ``is_deleted()`` and the copy: JAX's
    "Array has been deleted" becomes the take's own error."""
    arr = OWNED["f32_rank2"]()
    st = _stager(arr, "0/train/w7", is_async_snapshot=True)

    def donated_under_the_call(a):
        a.delete()
        return jax.device_put(a, may_alias=False)

    monkeypatch.setattr(array_preparer, "_own_copy", donated_under_the_call)
    with pytest.raises(DonatedBeforeStagedError, match=r"0/train/w7"):
        st.start_dtoh()
