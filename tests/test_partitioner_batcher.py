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
