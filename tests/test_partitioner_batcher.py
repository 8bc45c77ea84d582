"""Partitioner + batcher unit tests (reference tests/test_partitioner.py,
tests/test_batcher.py patterns, without multi-process)."""

import asyncio
import os

import numpy as np
import pytest

from tpusnap.batcher import batch_read_requests, batch_write_requests
from tpusnap.io_preparers.array import ArrayBufferStager, ArrayIOPreparer
from tpusnap.io_types import BufferConsumer, ReadReq, WriteReq
from tpusnap.knobs import override_slab_size_threshold_bytes
from tpusnap.manifest import TensorEntry
from tpusnap.partitioner import (
    _greedy_assign,
    consolidate_replicated_entries,
)
from tpusnap.telemetry import MetricsSink


def _tensor_entry(path, nbytes=100, replicated=True, location=None):
    return TensorEntry(
        location=location or f"replicated/{path}",
        serializer="buffer_protocol",
        dtype="uint8",
        shape=[nbytes],
        replicated=replicated,
    )


def test_greedy_assignment_balances():
    units = [(f"u{i}", [f"p{i}"], size) for i, size in enumerate([100, 90, 50, 40, 30, 10])]
    assignment = _greedy_assign(units, [0, 0, 0])
    loads = [0, 0, 0]
    for (key, _, size) in units:
        loads[assignment[key]] += size
    assert max(loads) - min(loads) <= 40  # largest-first greedy is balanced
    assert set(assignment.values()) == {0, 1, 2}


def test_greedy_respects_preexisting_load():
    units = [("u", ["p"], 10)]
    assignment = _greedy_assign(units, [1000, 0])
    assert assignment["u"] == 1


def test_consolidate_prefers_writer_batched_version():
    """The writer rank's slab-batched entry (location under batched/) must
    win over rank 0's unbatched copy — otherwise the manifest points at a
    blob nobody wrote (code-review regression)."""
    rank0 = {"m/w": _tensor_entry("m/w")}
    rank1_entry = _tensor_entry("m/w", location="batched/abc123")
    rank1_entry.byte_range = [0, 100]
    rank1 = {"m/w": rank1_entry}
    merged = consolidate_replicated_entries([rank0, rank1])
    assert merged["0/m/w"].location == "batched/abc123"
    assert merged["0/m/w"].byte_range == [0, 100]
    assert "1/m/w" not in merged


def test_consolidate_keeps_per_rank_entries():
    rank0 = {"m/x": _tensor_entry("m/x", replicated=False, location="0/m/x")}
    rank1 = {"m/x": _tensor_entry("m/x", replicated=False, location="1/m/x")}
    merged = consolidate_replicated_entries([rank0, rank1])
    assert merged["0/m/x"].location == "0/m/x"
    assert merged["1/m/x"].location == "1/m/x"


def test_batch_write_requests_packs_slabs(tmp_path):
    arrays = {f"a{i}": np.full(100, i, dtype=np.uint8) for i in range(10)}
    entries = {}
    write_reqs = []
    for name, arr in arrays.items():
        entry, reqs = ArrayIOPreparer.prepare_write(f"0/{name}", arr)
        entries[name] = entry
        write_reqs += reqs
    entries_list, reqs = batch_write_requests(list(entries.values()), write_reqs)
    assert len(reqs) == 1  # all ten 100B writes in one slab
    slab_req = reqs[0]
    assert slab_req.path.startswith("batched/")
    for entry in entries.values():
        assert entry.location == slab_req.path
        assert entry.byte_range is not None

    # stage the slab and check each member's byte range holds its data
    buf = asyncio.run(slab_req.buffer_stager.stage_buffer())
    mv = memoryview(buf)
    for name, arr in arrays.items():
        start, end = entries[name].byte_range
        assert bytes(mv[start:end]) == arr.tobytes()


def test_batch_write_respects_threshold():
    with override_slab_size_threshold_bytes(250):
        arrays = {f"a{i}": np.full(100, i, dtype=np.uint8) for i in range(5)}
        entries, write_reqs = {}, []
        for name, arr in arrays.items():
            entry, reqs = ArrayIOPreparer.prepare_write(f"0/{name}", arr)
            entries[name] = entry
            write_reqs += reqs
        _, reqs = batch_write_requests(list(entries.values()), write_reqs)
        # 5×100B with 250B slabs → 3 slabs (2+2+1); the singleton stays raw
        slab_reqs = [r for r in reqs if r.path.startswith("batched/")]
        assert len(slab_reqs) == 2
        assert len(reqs) == 3


class _CollectConsumer(BufferConsumer):
    def __init__(self, sink, key):
        self.sink, self.key = sink, key

    async def consume_buffer(self, buf, executor=None):
        self.sink[self.key] = bytes(buf)

    def get_consuming_cost_bytes(self):
        return 1


def test_batch_read_requests_merges_spans():
    sink = {}
    reqs = [
        ReadReq("loc", _CollectConsumer(sink, "a"), byte_range=(0, 10)),
        ReadReq("loc", _CollectConsumer(sink, "b"), byte_range=(10, 20)),
        ReadReq("loc", _CollectConsumer(sink, "c"), byte_range=(20, 32)),
        ReadReq("other", _CollectConsumer(sink, "d"), byte_range=(5, 9)),
    ]
    merged = batch_read_requests(reqs)
    assert len(merged) == 2
    span = [r for r in merged if r.path == "loc"][0]
    assert span.byte_range == (0, 32)
    data = bytes(range(32))
    asyncio.run(span.buffer_consumer.consume_buffer(data))
    assert sink["a"] == data[0:10] and sink["b"] == data[10:20] and sink["c"] == data[20:32]


def test_batch_read_skips_sparse_spans():
    sink = {}
    reqs = [
        ReadReq("loc", _CollectConsumer(sink, "a"), byte_range=(0, 10)),
        ReadReq("loc", _CollectConsumer(sink, "b"), byte_range=(1000, 1010)),
    ]
    merged = batch_read_requests(reqs)
    assert len(merged) == 2  # too sparse to merge


def test_batching_disabled_knob():
    from tpusnap.knobs import override_batching_disabled

    arrays = {f"a{i}": np.full(100, i, dtype=np.uint8) for i in range(4)}
    entries, write_reqs = {}, []
    for name, arr in arrays.items():
        entry, reqs = ArrayIOPreparer.prepare_write(f"0/{name}", arr)
        entries[name] = entry
        write_reqs += reqs
    with override_batching_disabled(True):
        _, reqs = batch_write_requests(list(entries.values()), write_reqs)
        assert len(reqs) == 4


class TestDeviceBatching:
    """Device-side slab packing (DeviceBatchedBufferStager) — the
    reference's GPUBatchedBufferStager analog done via XLA bitcast+concat
    and one DtoH DMA (reference batcher.py:101-159)."""

    def _prepare(self, arrays):
        entries, write_reqs = {}, []
        for name, arr in arrays.items():
            entry, reqs = ArrayIOPreparer.prepare_write(f"0/{name}", arr)
            entries[name] = entry
            write_reqs += reqs
        return entries, write_reqs

    def test_device_slab_packs_and_is_byte_exact(self):
        import jax.numpy as jnp

        from tpusnap.batcher import DeviceBatchedBufferStager

        arrays = {
            "f32": jnp.arange(32, dtype=jnp.float32),
            "bf16": jnp.arange(16, dtype=jnp.bfloat16),
            "i8": jnp.arange(-8, 8, dtype=jnp.int8),
            "bool": jnp.asarray([True, False] * 4),
        }
        entries, write_reqs = self._prepare(arrays)
        _, reqs = batch_write_requests(list(entries.values()), write_reqs)
        assert len(reqs) == 1
        assert isinstance(reqs[0].buffer_stager, DeviceBatchedBufferStager)
        buf = asyncio.run(reqs[0].buffer_stager.stage_buffer())
        mv = memoryview(buf).cast("B")
        for name, arr in arrays.items():
            start, end = entries[name].byte_range
            assert bytes(mv[start:end]) == np.asarray(arr).tobytes()

    def test_four_byte_members_are_packed_as_words_byte_exact(self):
        """A slab whose members are all 4-byte elements is concatenated as
        ``uint32`` on the device (a TPU pads a minor dimension of 4 bytes
        to 128) and read as the same bytes on the host."""
        import jax
        import jax.numpy as jnp

        from tpusnap.batcher import DeviceBatchedBufferStager, _pack_members

        arrays = {
            "f32": jax.random.normal(jax.random.PRNGKey(0), (3, 5, 7), jnp.float32),
            "i32": jnp.arange(-6, 7, dtype=jnp.int32),
            "u32": jnp.asarray([0, 1, 0xFFFFFFFF, 0x01020304], jnp.uint32),
            "scalar": jnp.asarray(7, jnp.int32),
        }
        assert _pack_members(tuple(arrays.values())).dtype == jnp.uint32
        assert _pack_members((arrays["f32"], jnp.ones(3, jnp.bfloat16))).dtype == jnp.uint8
        entries, write_reqs = self._prepare(arrays)
        _, reqs = batch_write_requests(list(entries.values()), write_reqs)
        assert len(reqs) == 1
        assert isinstance(reqs[0].buffer_stager, DeviceBatchedBufferStager)
        buf = asyncio.run(reqs[0].buffer_stager.stage_buffer())
        mv = memoryview(buf).cast("B")
        assert len(mv) == sum(a.nbytes for a in arrays.values())
        for name, arr in arrays.items():
            start, end = entries[name].byte_range
            assert bytes(mv[start:end]) == np.asarray(arr).tobytes()

    def test_mixed_host_device_members_split_slabs(self):
        import jax.numpy as jnp

        from tpusnap.batcher import (
            BatchedBufferStager,
            DeviceBatchedBufferStager,
        )

        arrays = {
            "host0": np.full(100, 1, np.uint8),
            "dev0": jnp.arange(25, dtype=jnp.float32),
            "host1": np.full(100, 2, np.uint8),
            "dev1": jnp.arange(25, dtype=jnp.float32),
        }
        entries, write_reqs = self._prepare(arrays)
        _, reqs = batch_write_requests(list(entries.values()), write_reqs)
        kinds = {type(r.buffer_stager) for r in reqs}
        assert kinds == {BatchedBufferStager, DeviceBatchedBufferStager}
        assert len(reqs) == 2

    def test_device_batching_disabled_knob(self):
        import jax.numpy as jnp

        from tpusnap.batcher import BatchedBufferStager
        from tpusnap.knobs import override_device_batching_disabled

        arrays = {f"a{i}": jnp.arange(16, dtype=jnp.float32) for i in range(4)}
        entries, write_reqs = self._prepare(arrays)
        with override_device_batching_disabled(True):
            _, reqs = batch_write_requests(list(entries.values()), write_reqs)
        assert len(reqs) == 1
        assert isinstance(reqs[0].buffer_stager, BatchedBufferStager)

    def test_snapshot_roundtrip_with_device_batching(self, tmp_path):
        """End-to-end: sharded + replicated jax arrays, slabs packed on
        device, bit-identical restore."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from tpusnap import PytreeState, Snapshot

        mesh = Mesh(np.asarray(jax.devices()).reshape(4, 2), ("x", "y"))
        sharded = jax.device_put(
            jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
            NamedSharding(mesh, P("x", "y")),
        )
        state = {
            "sharded": sharded,
            "small_a": jnp.arange(10, dtype=jnp.bfloat16),
            "small_b": jnp.arange(20, dtype=jnp.int8),
        }
        app_state = {"m": PytreeState(dict(state))}
        Snapshot.take(str(tmp_path / "snap"), app_state)

        target = {
            "sharded": jax.device_put(
                jnp.zeros((8, 8), jnp.float32), NamedSharding(mesh, P("x", "y"))
            ),
            "small_a": jnp.zeros(10, jnp.bfloat16),
            "small_b": jnp.zeros(20, jnp.int8),
        }
        restored = {"m": PytreeState(target)}
        Snapshot(str(tmp_path / "snap")).restore(restored)
        for key, want in state.items():
            got = restored["m"].tree[key]
            assert np.asarray(got).tobytes() == np.asarray(want).tobytes()


def test_estimate_matches_prepared_entries():
    """Drift guard: estimate_write_loads' unit ids and costs must agree
    with what prepare_write actually produces — the partition plan is
    computed from the estimates, then applied to the prepared entries,
    and any disagreement degrades into duplicate writes."""
    import functools

    import jax.numpy as jnp

    from tpusnap.io_preparer import prepare_write
    from tpusnap.knobs import override_max_chunk_size_bytes
    from tpusnap.manifest import ChunkedTensorEntry, PrimitiveEntry, TensorEntry
    from tpusnap.partitioner import estimate_write_loads

    def cast(path, arr, tracing):
        return arr.astype(jnp.bfloat16) if path.endswith("big") else arr

    with override_max_chunk_size_bytes(16 * 1024):
        flattened = {
            "m/big": np.zeros((64, 256), np.float32),      # chunked, casts
            "m/small": np.arange(100, dtype=np.float32),   # dense
            "m/scalar": np.float32(3.5),                   # np.generic
            "m/lr": 0.1,                                   # primitive
            "m/blob": {1, 2, 3},                           # pickled object
        }
        units, base, traced = estimate_write_loads(
            flattened, sorted(flattened), array_prepare_func=cast
        )
        # The traced geometry covers every dense array leaf.
        assert set(traced) == {"m/big", "m/small", "m/scalar"}
        unit_ids = {u for u, _ in units}
        unit_costs = dict(units)

        for path, leaf in flattened.items():
            entry, _ = prepare_write(
                obj=leaf,
                logical_path=path,
                rank=0,
                replicated=True,
                array_prepare_func=functools.partial(cast, path),
            )
            if isinstance(entry, PrimitiveEntry):
                assert (path, 0) in units
            elif isinstance(entry, ChunkedTensorEntry):
                for i, chunk in enumerate(entry.chunks):
                    uid = f"{path}::{i}"
                    assert uid in unit_ids, (uid, sorted(unit_ids))
                    from tpusnap.serialization import tensor_nbytes

                    assert unit_costs[uid] == tensor_nbytes(
                        chunk.tensor.dtype, chunk.tensor.shape
                    )
                assert f"{path}::{len(entry.chunks)}" not in unit_ids
            elif isinstance(entry, TensorEntry):
                assert path in unit_ids
                from tpusnap.serialization import tensor_nbytes

                assert unit_costs[path] == tensor_nbytes(
                    entry.dtype, entry.shape
                )
            else:  # ObjectEntry: getsizeof approximation, just present
                assert path in unit_ids


# ------------------------------------------------- who may be a slab member

_KIB, _MIB = 1 << 10, 1 << 20
# One state around the member size (16 MiB): two leaves under it, one at
# it, two over it and under the slab threshold (128 MiB).
_MIXED_BYTES = {"k1": _KIB, "m15": 15 * _MIB, "m16": 16 * _MIB, "m17": 17 * _MIB, "m60": 60 * _MIB}
_MIXED_MEMBERS = ("k1", "m15")
_MIXED_WHOLE = ("m16", "m17", "m60")


class _CounterSink(MetricsSink):
    """Sums each counter's deltas; every other event is the base's no-op."""

    def __init__(self):
        self.counters = {}

    def on_counter(self, name, delta, value):
        self.counters[name] = self.counters.get(name, 0) + delta


def _mixed_state(kind):
    import jax.numpy as jnp

    rng = np.random.default_rng(29)
    arrays = {
        name: rng.standard_normal(nbytes // 4, dtype=np.float32)
        for name, nbytes in _MIXED_BYTES.items()
    }
    return {n: jnp.asarray(a) for n, a in arrays.items()} if kind == "jax" else arrays


def _bits(arr):
    return np.asarray(arr).reshape(-1).view(np.uint8)


def _batch(arrays):
    """The batcher over one state's write requests: entries by name, the
    requests it returns, and the counters it bumped."""
    from tpusnap import metrics_sink

    entries, write_reqs = {}, []
    for name, arr in arrays.items():
        entry, reqs = ArrayIOPreparer.prepare_write(f"0/{name}", arr)
        entries[name] = entry
        write_reqs += reqs
    with metrics_sink(_CounterSink()) as sink:
        _, reqs = batch_write_requests(list(entries.values()), write_reqs)
    return entries, reqs, sink.counters


@pytest.fixture(scope="module", params=["jax", "numpy"])
def batched_mixed(request):
    return request.param, _batch(_mixed_state(request.param))


@pytest.mark.parametrize("name", list(_MIXED_BYTES))
def test_member_size_decides_who_is_a_slab_member(batched_mixed, name):
    """Under 16 MiB a leaf is a member (``location`` + ``byte_range`` of
    the one slab); at 16 MiB or more it is its own request and its own
    blob, as a leaf over the slab threshold is. Device and host leaves
    alike: the rule comes before the choice of stager."""
    from tpusnap.batcher import BatchedBufferStager, DeviceBatchedBufferStager

    kind, (entries, reqs, _) = batched_mixed
    slabs = [r for r in reqs if r.path.startswith("batched/")]
    assert len(slabs) == 1 and len(reqs) == 1 + len(_MIXED_WHOLE)
    assert isinstance(
        slabs[0].buffer_stager,
        DeviceBatchedBufferStager if kind == "jax" else BatchedBufferStager,
    )
    entry = entries[name]
    if name in _MIXED_MEMBERS:
        assert entry.location == slabs[0].path
        start, end = entry.byte_range
        assert end - start == _MIXED_BYTES[name]
        assert slabs[0].buffer_stager.total == sum(_MIXED_BYTES[n] for n in _MIXED_MEMBERS)
    else:
        assert entry.location == f"0/{name}" and entry.byte_range is None
        (own,) = [r for r in reqs if r.path == entry.location]
        assert isinstance(own.buffer_stager, ArrayBufferStager)


def test_whole_leaf_counters_say_what_the_member_size_kept_out(batched_mixed):
    _, (_, _, counters) = batched_mixed
    assert counters["batcher.whole_leaves"] == len(_MIXED_WHOLE)
    assert counters["batcher.whole_leaf_bytes"] == sum(_MIXED_BYTES[n] for n in _MIXED_WHOLE)


@pytest.mark.parametrize(
    "threshold, members, whole",
    [
        # Below the member size the threshold bounds members as before,
        # and nothing is "kept out by the member size".
        (8 * _MIB, ("k1", "m4"), ()),
        (_MIB, (), ()),
        # At or above it the member size does: the capacity is the
        # threshold's, the largest member is not. (At 16 MiB the 12 MiB
        # leaf is a candidate, but with k1 and m4 it passes the capacity:
        # flushed as a slab of one, it is left as its own request.)
        (16 * _MIB, ("k1", "m4"), ()),
        (32 * _MIB, ("k1", "m4", "m12"), ("m16", "m20")),
        (128 * _MIB, ("k1", "m4", "m12"), ("m16", "m20")),
    ],
)
def test_threshold_below_member_size_still_bounds_members(threshold, members, whole):
    sizes = {"k1": _KIB, "m4": 4 * _MIB, "m12": 12 * _MIB, "m16": 16 * _MIB, "m20": 20 * _MIB}
    arrays = {n: np.full(b, i + 1, np.uint8) for i, (n, b) in enumerate(sizes.items())}
    with override_slab_size_threshold_bytes(threshold):
        entries, reqs, counters = _batch(arrays)
    got = tuple(n for n in sizes if entries[n].byte_range is not None)
    assert got == members
    assert len({entries[n].location for n in members}) == (1 if members else 0)
    assert counters.get("batcher.whole_leaves", 0) == len(whole)
    assert counters.get("batcher.whole_leaf_bytes", 0) == sum(sizes[n] for n in whole)
    assert len(reqs) == len(sizes) - len(members) + (1 if members else 0)


@pytest.mark.parametrize(
    "sizes",
    [
        {"k1": _KIB, "m16": 16 * _MIB, "m20": 20 * _MIB},  # one candidate
        {"m16": 16 * _MIB, "m20": 20 * _MIB},  # none
    ],
    ids=["one_candidate", "no_candidate"],
)
def test_fewer_than_two_candidates_leave_every_request_as_it_was(sizes):
    """The early return: with under two leaves below the member size no
    slab is made, every leaf keeps its own request and location, and the
    counters still say which leaves the member size kept whole."""
    arrays = {n: np.full(b, i + 1, np.uint8) for i, (n, b) in enumerate(sizes.items())}
    entries, reqs, counters = _batch(arrays)
    assert [r.path for r in reqs] == [f"0/{n}" for n in sizes]
    assert all(isinstance(r.buffer_stager, ArrayBufferStager) for r in reqs)
    assert all(e.location == f"0/{n}" and e.byte_range is None for n, e in entries.items())
    whole = [n for n, b in sizes.items() if b >= 16 * _MIB]
    assert counters["batcher.whole_leaves"] == len(whole)
    assert counters["batcher.whole_leaf_bytes"] == sum(sizes[n] for n in whole)


def _restore_bits(path, state):
    import jax
    import jax.numpy as jnp

    from tpusnap import PytreeState, Snapshot

    targets = {"m": PytreeState(jax.tree.map(jnp.zeros_like, state))}
    Snapshot(path).restore(targets)
    for name, want in state.items():
        got = targets["m"].tree[name]
        assert got.dtype == want.dtype and got.shape == want.shape
        assert np.array_equal(_bits(got), _bits(want)), name


@pytest.mark.parametrize("how", ["take", "async_take"])
def test_mixed_state_round_trips_with_whole_leaves_beside_a_slab(tmp_path, monkeypatch, how):
    """The five leaves through a real take: two in one slab packed on the
    device, three as blobs of their own; every checksum recorded, ``verify``
    clean, restored bit for bit; ``batcher.device_slab_bytes`` holds the
    members' bytes only and ``storage.writes`` one blob a whole leaf."""
    from tpusnap import PytreeState, Snapshot, metrics_sink, telemetry

    monkeypatch.setenv("TPUSNAP_TELEMETRY", "1")
    state = _mixed_state("jax")
    path = str(tmp_path / "snap")
    telemetry.reset_global_counters()
    with metrics_sink(_CounterSink()) as sink:
        if how == "take":
            Snapshot.take(path, {"m": PytreeState(state)})
        else:
            Snapshot.async_take(path, {"m": PytreeState(state)}).wait()
    manifest = Snapshot(path).get_manifest()
    by_name = {n: manifest[f"0/m/{n}"] for n in state}
    slab = {by_name[n].location for n in _MIXED_MEMBERS}
    assert len(slab) == 1 and next(iter(slab)).startswith("batched/")
    for name, entry in by_name.items():
        assert entry.checksum is not None, name
        assert (entry.byte_range is not None) == (name in _MIXED_MEMBERS)
        if name in _MIXED_WHOLE:
            assert not entry.location.startswith("batched/")
            assert os.path.getsize(os.path.join(path, entry.location)) == _MIXED_BYTES[name]
    members_bytes = sum(_MIXED_BYTES[n] for n in _MIXED_MEMBERS)
    assert sink.counters["batcher.device_slabs"] == 1
    assert sink.counters["batcher.device_slab_bytes"] == members_bytes
    assert sink.counters["batcher.whole_leaves"] == len(_MIXED_WHOLE)
    assert sink.counters["batcher.whole_leaf_bytes"] == sum(_MIXED_BYTES[n] for n in _MIXED_WHOLE)
    assert telemetry.counter_value("batcher.device_pack_fallbacks") == 0
    # One blob a whole leaf and one for the slab, besides the take's own
    # small objects (which no tensor entry points at).
    tensor_blobs = {e.location for e in by_name.values()}
    assert len(tensor_blobs) == 1 + len(_MIXED_WHOLE)
    assert sink.counters["storage.writes"] >= len(tensor_blobs)
    report = Snapshot(path).verify()
    assert report.clean and report.unverified == 0
    _restore_bits(path, state)


@pytest.mark.parametrize("written_under", ["old", "new"])
def test_slab_layouts_restore_across_the_member_size(tmp_path, monkeypatch, written_under):
    """The format did not change. A snapshot written in the old layout
    (the member size at the slab threshold: 60 MiB members inside slabs)
    restores bit for bit under the default, and one written under the
    default restores under the old member size."""
    import tpusnap.batcher as batcher
    from tpusnap import PytreeState, Snapshot

    default = batcher._MAX_SLAB_MEMBER_BYTES
    assert default == 16 * _MIB
    old = 128 * _MIB
    state = _mixed_state("jax")
    path = str(tmp_path / "snap")
    monkeypatch.setattr(batcher, "_MAX_SLAB_MEMBER_BYTES", old if written_under == "old" else default)
    Snapshot.take(path, {"m": PytreeState(state)})
    manifest = Snapshot(path).get_manifest()
    in_slabs = {n for n in state if manifest[f"0/m/{n}"].byte_range is not None}
    if written_under == "old":
        # 109 MiB in all, under the 128 MiB capacity: one slab holds the five.
        assert in_slabs == set(state)
        assert len({manifest[f"0/m/{n}"].location for n in state}) == 1
    else:
        assert in_slabs == set(_MIXED_MEMBERS)
    monkeypatch.setattr(batcher, "_MAX_SLAB_MEMBER_BYTES", default if written_under == "old" else old)
    assert Snapshot(path).verify().clean
    _restore_bits(path, state)
