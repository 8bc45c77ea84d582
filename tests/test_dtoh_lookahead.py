"""When a leaf's copy to the host is started (PR 41): never when its
stager is built, by the write scheduler when its dispatch reaches the
request or a fixed depth before, and by the codec policy's sampler for
its one source. The depth is PR 41's where the caller's steps may run
beside the copies, and four times that, or the take's host-memory budget
where it is less, where none can (PR 52: the caller stands in the take, or
in ``wait_staged()``). Counts and orders on the CPU, never a rate."""

import asyncio
import importlib.util
import io
import json
import logging
import os
from contextlib import redirect_stdout

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpusnap import PytreeState, Snapshot, _native, telemetry
from tpusnap import compress as compress_mod
from tpusnap import scheduler as scheduler_mod
from tpusnap.batcher import BatchedBufferStager, DeviceBatchedBufferStager
from tpusnap.io_preparers.array import ArrayBufferStager, DonatedBeforeStagedError
from tpusnap.io_types import BufferStager, WriteReq
from tpusnap.knobs import override_compress, override_stage_threads
from tpusnap.manifest import TensorEntry
from tpusnap.scheduler import _WriteScheduler, execute_write_reqs
from tpusnap.serialization import dtype_to_string
from tpusnap.storage_plugins.fs import FSStoragePlugin

MIB = 1 << 20
DEPTH = scheduler_mod._DTOH_LOOKAHEAD_BYTES
DEEP = scheduler_mod._DTOH_LOOKAHEAD_BYTES_NO_STEPS
REQS = scheduler_mod._DTOH_LOOKAHEAD_REQS


def _enqueued() -> int:
    return telemetry.counter_value("dtoh.enqueued_bytes")


def _entry(arr, location="0/w") -> TensorEntry:
    return TensorEntry(
        location=location,
        serializer="buffer_protocol",
        dtype=dtype_to_string(arr.dtype),
        shape=list(arr.shape),
        replicated=False,
    )


def _stager(arr, location="0/w", **kwargs) -> ArrayBufferStager:
    return ArrayBufferStager(arr, entry=_entry(arr, location), **kwargs)


def _leaf_stagers(write_reqs):
    """Every array leaf's stager, a slab's members among them."""
    for wr in write_reqs:
        st = wr.buffer_stager
        members = getattr(st, "members", None)
        for leaf in [s for _, _, s in members] if members is not None else [st]:
            if isinstance(leaf, ArrayBufferStager):
                yield leaf


# ------------------------------------------------- (a) prepare starts nothing


@pytest.mark.parametrize("kind", ["async", "sync"])
def test_prepare_starts_no_copy_and_the_take_starts_each_once(tmp_path, monkeypatch, kind):
    """When the scheduler is handed a take's requests no copy has been
    started and none counted; when the take is done every leaf's has,
    once: the state's bytes, a slab's members among them (their second
    crossing, inside the slab, is the slab's own fetch)."""
    import tpusnap.snapshot as snapshot_mod

    monkeypatch.setenv("TPUSNAP_SLAB_SIZE_THRESHOLD_BYTES", str(MIB))
    big = {f"w{i}": jnp.full((512, 1024), float(i), jnp.float32) for i in range(3)}
    small = {f"b{i}": jnp.full((256,), float(i), jnp.float32) for i in range(6)}
    state = {"big": big, "small": small}
    nbytes = sum(x.nbytes for x in jax.tree.leaves(state))
    seen = {}
    run = snapshot_mod.sync_execute_write_reqs

    def at_the_scheduler(write_reqs, *args, **kwargs):
        leaves = list(_leaf_stagers(write_reqs))
        seen["slabs"] = sum(
            isinstance(wr.buffer_stager, DeviceBatchedBufferStager) for wr in write_reqs
        )
        seen["leaves"] = len(leaves)
        seen["started"] = [s for s in leaves if s.dtoh_started is not None]
        seen["enqueued"] = _enqueued() - before
        return run(write_reqs, *args, **kwargs)

    monkeypatch.setattr(snapshot_mod, "sync_execute_write_reqs", at_the_scheduler)
    before = _enqueued()
    path = str(tmp_path / "snap")
    if kind == "async":
        Snapshot.async_take(path, {"train": PytreeState(state)}).wait()
    else:
        Snapshot.take(path, {"train": PytreeState(state)})
    assert seen["slabs"] == 1 and seen["leaves"] == 9
    assert seen["started"] == [] and seen["enqueued"] == 0
    assert _enqueued() - before == nbytes
    counters = telemetry.LAST_TAKE_SUMMARY["counters"]
    assert counters["dtoh.enqueued_bytes"] == nbytes
    assert counters.get("dtoh.cold_fetches", 0) == 0
    assert counters["dtoh.lookahead_starts"] == 3  # four requests: all but the first
    target = {"train": PytreeState(jax.tree.map(jnp.zeros_like, state))}
    Snapshot(path).restore(target)
    assert all(
        np.array_equal(a, b)
        for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(target["train"].tree))
    )


# --------------------------------------------- (b) the scheduler's order and depth


class _Recorded(BufferStager):
    """Says when its copy is started and when it is staged; its size is
    what it declares (no byte of it is allocated)."""

    def __init__(self, log, name, nbytes, copies=True):
        self.log, self.name, self.nbytes, self.copies = log, name, nbytes, copies
        self.started = False

    def start_dtoh(self) -> int:
        if self.copies and not self.started:
            self.started = True
            self.log.append(("start", self.name))
        return self.nbytes if self.copies else 0

    async def stage_buffer(self, executor=None):
        self.log.append(("stage", self.name))
        await asyncio.sleep(0.001)
        self.log.append(("staged", self.name))
        return b"x" * 8

    def get_staging_cost_bytes(self) -> int:
        return self.nbytes

    def aliases_caller_memory(self) -> bool:
        return False  # as an accelerator's leaf: an async take returns before it is staged


# Who could dispatch a step while the copies cross: the caller of a
# pipelined ``async_take`` that has returned; nobody once that caller
# stands in ``wait_staged()``, and nobody under ``take``.
WHO = ["a_step_may_run", "the_caller_waits", "the_caller_stands_in_the_take"]


def _run_recorded(tmp_path, sizes, host=(), threads=1, who="a_step_may_run", budget=1 << 40):
    """One pass of the write scheduler over recording stagers of
    ``sizes`` (those at the indices ``host`` have no copy to start);
    returns the log, the queue's order and the take's summary."""
    log = []
    stagers = [
        _Recorded(log, f"r{i}", n, copies=i not in host) for i, n in enumerate(sizes)
    ]
    write_reqs = [WriteReq(path=s.name, buffer_stager=s) for s in stagers]
    # Largest first, request order among equals: the scheduler's order.
    order = [s.name for s in sorted(stagers, key=lambda s: -s.nbytes)]
    rec = telemetry.TakeTelemetry(rank=0, enabled=False)

    async def go():
        with telemetry.use(rec):
            pending = await execute_write_reqs(
                write_reqs, FSStoragePlugin(str(tmp_path)), budget, rank=0,
                pipelined_staging=who != "the_caller_stands_in_the_take",
            )
            if who == "the_caller_waits":
                # The take has returned with its first requests dispatched;
                # its caller comes back to wait for the rest.
                log.append(("caller_waits", order[0]))
                with pending.caller_waits():
                    await pending.complete()
            else:
                await pending.complete()

    with override_stage_threads(threads):
        asyncio.run(go())
    return log, order, {s.name: s for s in stagers}, rec.summary()


@pytest.mark.parametrize(
    "sizes,host,threads",
    [
        ([256 * MIB] * 6, (), 1),
        ([64 * MIB] * 12, (), 1),
        ([16 * MIB] * 40, (), 1),
        ([1024 * MIB] * 3, (), 1),
        ([256 * MIB] * 6 + [192 * MIB] * 9 + [64 * MIB] * 3 + [405508], (), 1),
        ([64 * MIB] * 12, (0, 1, 5, 11), 1),
        ([256 * MIB] * 6 + [64 * MIB] * 6, (), 2),
    ],
    ids=["256MiB", "64MiB", "16MiB", "over_the_depth", "the_dense_cell", "host_leaves_between", "two_threads"],
)
@pytest.mark.parametrize("who", WHO)
def test_copies_start_in_staging_order_a_fixed_depth_ahead(tmp_path, sizes, host, threads, who):
    """The depth is ``_DTOH_LOOKAHEAD_BYTES`` beside a caller that may
    step. With nobody to step it is ``_DTOH_LOOKAHEAD_BYTES_NO_STEPS``
    (the host-memory budget is larger here): that much is under way at
    the first dispatch (``take``), or as soon as the caller has come to
    wait, and what was started past the stepping depth is counted."""
    log, order, by_name, summary = _run_recorded(tmp_path, sizes, host=host, threads=threads, who=who)
    copying = [n for n in order if by_name[n].copies]
    starts = [n for kind, n in log if kind == "start"]
    assert starts == copying  # in the queue's order, each once
    assert [n for kind, n in log if kind == "stage"] == order
    position = {n: i for i, n in enumerate(order)}
    started, unfetched, dispatched, peak, ahead_starts = set(), 0, -1, 0, 0
    deep, deep_starts, deep_bytes = who == "the_caller_stands_in_the_take", 0, 0
    n_staged = 0  # with one thread, the request being dispatched when a copy starts
    for kind, name in log:
        i = position[name]
        if kind == "caller_waits":
            deep = True
        elif kind == "stage":
            dispatched = max(dispatched, i)
            # Its own copy before its staging, and the next request's too.
            due = [n for n in order[i : i + 1 + REQS] if by_name[n].copies]
            assert set(due) <= started, (name, due)
        elif kind == "staged":
            unfetched -= by_name[name].nbytes if by_name[name].copies else 0
            n_staged += 1
        else:
            # Never more than the depth ahead of the last request
            # dispatched: the next one always, further ones while under
            # the depth. (A request is dispatched, and its lookahead
            # run, before its staging is logged: hence `+ threads`.)
            within = i <= dispatched + threads + REQS or unfetched < DEPTH
            assert within or (deep and unfetched < DEEP), (name, dispatched, unfetched)
            if not (i <= n_staged + REQS or unfetched < DEPTH):  # past PR 41's depth
                deep_starts, deep_bytes = deep_starts + 1, deep_bytes + by_name[name].nbytes
            ahead_starts += i > dispatched + 1
            started.add(name)
            unfetched += by_name[name].nbytes
            peak = max(peak, unfetched)
    assert unfetched == 0
    largest = max(sizes)
    bound = max(DEPTH + largest, (1 + REQS) * largest)
    gauge = summary["gauges"]["dtoh.unfetched_bytes"]
    counters = summary["counters"]
    if who == "a_step_may_run":
        assert peak <= gauge <= bound + (threads - 1) * largest, (peak, gauge, bound)
        assert "dtoh.deep_starts" not in counters and "dtoh.deep_bytes" not in counters
    else:
        # The caller's arrival (or the take's first dispatch) starts what
        # it finds unstarted, up to the depth, before another request is staged.
        arrival = log.index(("caller_waits", order[0])) if who == "the_caller_waits" else 0
        staged_next = [k for k, e in enumerate(log) if e[0] == "stage" and k > arrival]
        before_it = log[: staged_next[0]] if staged_next else log
        at_arrival = sum(by_name[n].nbytes for kind, n in before_it if kind == "start")
        assert at_arrival >= min(DEEP, sum(by_name[n].nbytes for n in copying))
        assert peak == gauge <= max(DEEP + largest, (1 + REQS) * largest) + (threads - 1) * largest
        if threads == 1:
            assert counters.get("dtoh.deep_starts", 0) == deep_starts
            assert counters.get("dtoh.deep_bytes", 0) == deep_bytes
        assert deep_starts > 0 or sum(by_name[n].nbytes for n in copying[: 1 + REQS]) + largest > DEPTH
    lookahead = counters["dtoh.lookahead_starts"]
    # All but the first request's own, which the first dispatch starts.
    assert 0 < lookahead <= len(copying) - (by_name[order[0]].copies)
    if threads == 1:
        assert lookahead == ahead_starts


@pytest.mark.parametrize("threads", [1, 2], ids=["one_thread", "two_threads"])
@pytest.mark.parametrize("who", WHO[1:])
def test_with_nobody_to_step_the_host_memory_budget_is_the_depth(tmp_path, who, threads):
    """Twelve leaves of 64 MiB under a host-memory budget of 512 MiB,
    which is past PR 41's depth and short of the state: the bytes started
    and not yet staged stop at the budget (a leaf may straddle it, as one
    straddles the stepping depth), and further copies start as leaves are
    staged."""
    budget, leaf = 512 * MIB, 64 * MIB
    assert DEPTH < budget < 12 * leaf
    log, order, by_name, summary = _run_recorded(
        tmp_path, [leaf] * 12, threads=threads, who=who, budget=budget
    )
    assert [n for kind, n in log if kind == "start"] == order
    unfetched = peak = 0
    for kind, name in log:
        if kind == "start":
            unfetched += leaf
            peak = max(peak, unfetched)
        elif kind == "staged":
            unfetched -= leaf
    assert peak == summary["gauges"]["dtoh.unfetched_bytes"] == budget
    counters = summary["counters"]
    assert counters["dtoh.deep_bytes"] == counters["dtoh.deep_starts"] * leaf
    # Past the stepping depth from the first dispatch on: every leaf but
    # those that the stepping depth itself would have had under way then.
    assert 12 - DEPTH // leaf - threads <= counters["dtoh.deep_starts"] <= 12 - DEPTH // leaf


@pytest.mark.parametrize("leaves,leaf_kib,depth_kib", [(6, 64, 64), (8, 64, 128), (12, 16, 64)],
                         ids=["a_leaf_a_depth", "two_leaves_a_depth", "four_leaves_a_depth"])
def test_owned_copies_alive_on_the_chip_are_bounded_by_the_depth(tmp_path, monkeypatch, leaves, leaf_kib, depth_kib):
    """PR 51: a large accelerator leaf crosses from a copy on the chip
    that tpusnap owns, made when the lookahead reaches the leaf and let
    go when its bytes are seen on the host. So the copies alive at once
    are the copies started and not yet staged: the one being staged and
    the depth ahead of it, never the state. (A CPU array never
    qualifies: the rule's backend test and the device's free bytes are
    patched. A pipelined take, where steps may run beside the drain.)"""
    from tpusnap.io_preparers import array as array_preparer

    leaf, depth = leaf_kib * 1024, depth_kib * 1024
    monkeypatch.setattr(array_preparer, "_lies_on_one_accelerator", lambda arr: True)
    monkeypatch.setattr(array_preparer, "_device_free_bytes", lambda device: 1 << 40)
    monkeypatch.setattr(array_preparer, "RELAYOUT_MIN_BYTES", 4096)
    monkeypatch.setattr(scheduler_mod, "_DTOH_LOOKAHEAD_BYTES", depth)
    arrays = [jnp.full((leaf // 4,), float(i), jnp.float32) for i in range(leaves)]
    stagers = [_stager(x, f"0/w{i}") for i, x in enumerate(arrays)]
    write_reqs = [WriteReq(path=s.entry.location, buffer_stager=s) for s in stagers]
    plain, held = array_preparer._own_copy, []

    def counted(arr):
        # The copies tpusnap holds while one more is made, that one included.
        held.append(1 + sum(s._owned is not None for s in stagers))
        return plain(arr)

    monkeypatch.setattr(array_preparer, "_own_copy", counted)
    before = telemetry.counter_value("dtoh.owned_leaves")

    async def go():
        pending = await execute_write_reqs(
            write_reqs, FSStoragePlugin(str(tmp_path)), 1 << 30, rank=0, pipelined_staging=True
        )
        await pending.complete()

    with override_stage_threads(1):
        asyncio.run(go())
    assert len(held) == leaves == telemetry.counter_value("dtoh.owned_leaves") - before
    # The leaf being staged, and what the depth lets start ahead of it:
    # the next request always, further ones while under the depth's bytes.
    assert max(held) <= 1 + max(REQS, -(-depth // leaf))
    assert max(held) >= 2  # there is a lookahead: a copy is made beside one that is held
    assert all(s._owned is None for s in stagers)
    for i, x in enumerate(arrays):
        assert (tmp_path / "0" / f"w{i}").read_bytes() == np.asarray(x).tobytes()


def test_copies_are_started_ahead_of_a_head_that_waits_for_budget(tmp_path, monkeypatch):
    """The budget admits one request at a time and its write does not
    end: the head of the queue waits, with its copy under way (that is
    the prefetch) and no copy beyond the depth."""
    monkeypatch.setattr(scheduler_mod, "_DTOH_LOOKAHEAD_BYTES", 4096)
    log = []

    class Real(_Recorded):
        async def stage_buffer(self, executor=None):
            await super().stage_buffer(executor)
            return b"x" * self.nbytes

    stagers = [Real(log, f"r{i}", 4096) for i in range(5)]
    write_reqs = [WriteReq(path=s.name, buffer_stager=s) for s in stagers]

    class Held(FSStoragePlugin):
        async def write(self, write_io):
            await gate.wait()
            await super().write(write_io)

    gate = asyncio.Event()

    async def go():
        sched = _WriteScheduler(write_reqs, Held(str(tmp_path)), 4096, rank=0)
        drain = asyncio.ensure_future(sched.drain())
        for _ in range(200):
            await asyncio.sleep(0.001)
            if sched.io_tasks and sched._staging_budget_starved():
                break
        assert sched._staging_budget_starved()
        waiting = [n for kind, n in log if kind == "start"]
        staged = [n for kind, n in log if kind == "stage"]
        gate.set()
        await drain
        return waiting, staged, sched.dtoh_unfetched_bytes

    waiting, staged, left = asyncio.run(go())
    assert staged == ["r0"] and waiting == ["r0", "r1"]
    assert [n for kind, n in log if kind == "start"] == [s.name for s in stagers]
    assert left == 0


def test_a_stager_without_the_method_starts_nothing(tmp_path):
    """A plugin's stager from before ``start_dtoh`` existed, and the
    base class's default: dispatched as ever, nothing counted."""

    class Duck:
        async def stage_buffer(self, executor=None):
            return b"duck"

        def get_staging_cost_bytes(self):
            return 4

    class Plain(BufferStager):
        async def stage_buffer(self, executor=None):
            return b"plain"

        def get_staging_cost_bytes(self):
            return 5

    write_reqs = [
        WriteReq(path="duck", buffer_stager=Duck()),
        WriteReq(path="plain", buffer_stager=Plain()),
    ]
    rec = telemetry.TakeTelemetry(rank=0, enabled=False)

    async def go():
        with telemetry.use(rec):
            pending = await execute_write_reqs(
                write_reqs, FSStoragePlugin(str(tmp_path)), 1 << 20, rank=0
            )
            await pending.complete()

    asyncio.run(go())
    assert (tmp_path / "duck").read_bytes() == b"duck"
    assert (tmp_path / "plain").read_bytes() == b"plain"
    summary = rec.summary()
    assert "dtoh.lookahead_starts" not in summary["counters"]
    assert "dtoh.unfetched_bytes" not in summary["gauges"]


# ------------------------------------------------- (c) the stager's own method


def _prepare_same(arr, tracing):
    return arr


@pytest.mark.parametrize(
    "make,copies,cold",
    [
        (lambda a: _stager(jnp.asarray(a)), True, 0),
        (lambda a: _stager(jnp.asarray(a), array_prepare_func=_prepare_same), False, 1),
        (lambda a: _stager(a), False, 0),
        (lambda a: _stager(a, array_prepare_func=_prepare_same), False, 0),
    ],
    ids=["device_leaf", "device_leaf_behind_a_transform", "numpy_leaf", "numpy_leaf_behind_a_transform"],
)
def test_start_dtoh_counts_once_and_only_a_leaf_behind_a_transform_is_fetched_cold(make, copies, cold):
    arr = np.arange(64 * 1024, dtype=np.float32)
    before = _enqueued()
    st = make(arr)
    assert st.dtoh_started is None and _enqueued() == before  # built: nothing started
    want = arr.nbytes if copies else 0
    assert st.start_dtoh() == want
    stamp = st.dtoh_started
    assert (stamp is not None) == copies
    assert st.start_dtoh() == want and st.dtoh_started == stamp  # twice: once
    assert _enqueued() - before == want
    cold_before = telemetry.counter_value("dtoh.cold_fetches")
    staged = st._stage_blocking()
    assert bytes(memoryview(staged)) == arr.tobytes()
    assert telemetry.counter_value("dtoh.cold_fetches") - cold_before == cold
    assert _enqueued() - before == want


@pytest.mark.parametrize("slab", [BatchedBufferStager, DeviceBatchedBufferStager])
def test_a_slab_starts_its_members_copies(slab):
    """Both kinds: the host slab fetches them; the device slab's are
    thrown away when its pack succeeds, and counted all the same."""
    leaves = [jnp.full((1024,), float(i), jnp.float32) for i in range(3)]
    members, offset = [], 0
    for i, leaf in enumerate(leaves):
        members.append((offset, leaf.nbytes, _stager(leaf, f"0/b{i}")))
        offset += leaf.nbytes
    st = slab(members)
    before = _enqueued()
    assert st.start_dtoh() == offset == st.start_dtoh()
    assert _enqueued() - before == offset
    assert all(m.dtoh_started is not None for _, _, m in members)
    staged = asyncio.run(st.stage_buffer())
    assert bytes(memoryview(staged)) == b"".join(np.asarray(x).tobytes() for x in leaves)


# --------------------------------------------------------- (d) the sampler


def _bf16ish(shape, seed):
    """f32 values that carry bf16 precision: the codec halves them."""
    x = np.random.default_rng(seed).standard_normal(shape).astype(np.float32)
    return (x.view(np.uint32) & np.uint32(0xFFFF0000)).view(np.float32)


@pytest.mark.skipif(
    not _native.compression_available(), reason="native codec unavailable (no toolchain)"
)
def test_the_sampler_starts_its_source_alone_and_decides_as_on_host_bytes(monkeypatch):
    """The source is the first of the largest eligible leaves, which is
    the request the scheduler dispatches first; its copy is the only
    one started before scheduling; and ratio, bytes and decision are
    what the same values give as a numpy leaf (the parent's reading)."""
    monkeypatch.setattr(compress_mod, "AUTO_MIN_TAKE_BYTES", 1 << 18)
    monkeypatch.setattr(compress_mod, "pipe_ceiling_key", lambda storage: "X41")
    compress_mod.note_pipe_ceiling("X41", 0.001)
    values = {
        "a": _bf16ish((256, 1024), 1),
        "b": _bf16ish((1024, 1024), 2),  # the first of the two largest
        "c": _bf16ish((1024, 1024), 3),
        "d": _bf16ish((512, 1024), 4),
    }

    def decide(as_leaf):
        reqs = [
            WriteReq(path=f"0/{k}", buffer_stager=_stager(as_leaf(v), f"0/{k}"))
            for k, v in values.items()
        ]
        before = _enqueued()
        with override_compress(mode="auto", min_blob_bytes=65536):
            d = compress_mod.apply_take_policy(reqs, None, None, rec=None)
        return reqs, d, _enqueued() - before

    reqs, d, enqueued = decide(jnp.asarray)
    by_path = {wr.path: wr.buffer_stager for wr in reqs}
    assert [p for p, s in by_path.items() if s.dtoh_started is not None] == ["0/b"]
    assert enqueued == values["b"].nbytes
    sched = _WriteScheduler(reqs, None, 1 << 30, rank=0)
    try:
        assert sched.pipelines[0].write_req.buffer_stager is by_path["0/b"]
        counted = _enqueued()
        sched.pipelines.popleft()  # the first dispatch, as far as the copies go:
        sched._start_dtoh_ahead()  # it finds the source's under way, no second one
        total = sum(v.nbytes for v in values.values())
        assert sched.dtoh_unfetched_bytes == total  # the source's among them
        assert _enqueued() - counted == total - values["b"].nbytes
    finally:
        sched.executor.shutdown()
        sched.hash_executor.shutdown()
    _, on_host, none_started = decide(lambda v: v)
    assert none_started == 0
    assert (d.compress, d.reason) == (on_host.compress, on_host.reason) == (True, "codec_outruns_pipe")
    assert d.sample_ratio == on_host.sample_ratio and 0.3 < d.sample_ratio < 0.5
    assert d.sample_bytes == on_host.sample_bytes == values["b"].nbytes


# ------------------------------------------------------ (e) a donated leaf


@pytest.mark.parametrize("deleted", ["before_the_start", "after_the_start"])
def test_a_leaf_donated_before_it_is_staged_still_fails_by_name(tmp_path, caplog, deleted):
    """The lookahead reaches a deleted array: it starts nothing and
    logs nothing, and staging fails the take by the leaf's name."""
    leaves = [jnp.full((4096,), float(i), jnp.float32) for i in range(3)]
    stagers = [_stager(x, f"0/w{i}", is_async_snapshot=True) for i, x in enumerate(leaves)]
    if deleted == "after_the_start":
        assert stagers[2].start_dtoh() == leaves[2].nbytes
    leaves[2].delete()  # what donating it to a jitted step does
    write_reqs = [WriteReq(path=s.entry.location, buffer_stager=s) for s in stagers]

    async def go():
        pending = await execute_write_reqs(
            write_reqs, FSStoragePlugin(str(tmp_path)), 1 << 30, rank=0
        )
        await pending.complete()

    with caplog.at_level(logging.WARNING, logger="tpusnap"):
        with pytest.raises(DonatedBeforeStagedError, match=r"0/w2.*wait_staged\(\)"):
            asyncio.run(go())
    assert [r for r in caplog.records if r.name.startswith("tpusnap")] == []
    assert (stagers[2].dtoh_started is None) == (deleted == "before_the_start")


# ----------------------------------------------------------- the probe


def test_the_overlap_probe_runs_its_cases_and_prints_counts_not_times():
    """``scripts/dtoh_overlap_probe.py`` on the CPU: every case runs,
    the counts print, every time prints as null."""
    path = os.path.join(os.path.dirname(__file__), "..", "scripts", "dtoh_overlap_probe.py")
    spec = importlib.util.spec_from_file_location("dtoh_overlap_probe", path)
    probe = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(probe)
    out = io.StringIO()
    with redirect_stdout(out):
        rc = probe.main(
            ["--leaves", "4", "--leaf-mib", "1", "--steps", "3", "--step-iters", "2", "--ahead", "0", "1", "2"]
        )
    assert rc == 0
    head, *cases = [json.loads(line) for line in out.getvalue().splitlines()]
    assert head["device"]["platform"] == "cpu" and head["timed"] is False
    assert [c["case"] for c in cases] == [
        "quiet", "all_at_once", "all_at_once_unfetched", "ahead_0", "ahead_1", "ahead_2",
    ]
    for c in cases:
        quiet = c["case"] == "quiet"
        assert c["copies_started"] == c["leaves_fetched"] == (0 if quiet else 4)
        assert c["bytes_started"] == (0 if quiet else 4 * MIB) and c["steps"] == 3
        assert c["steps_ms"] is None and c["sum_ms"] is None and c["landed_ms"] is None
