"""Chunked-array preparer: arrays larger than max_chunk_size are split
along dim 0 into independently staged/written chunks, enabling pipelined
DtoH/IO and per-chunk write-load partitioning.

Counterpart of /root/reference/torchsnapshot/io_preparers/chunked_tensor.py.
Chunk slicing of a jax.Array is a device-side slice (an XLA computation
producing a chunk-sized buffer), so only one chunk of extra HBM is live at
a time; host memory is bounded by the scheduler's budget as usual.
"""

from __future__ import annotations

import math
from typing import List, Optional, Tuple

import jax
import numpy as np

from ..io_types import Future, ReadReq, WriteReq
from ..knobs import get_max_chunk_size_bytes
from ..manifest import Chunk, ChunkedTensorEntry, TensorEntry
from ..serialization import Serializer, dtype_to_string, string_to_dtype, tensor_nbytes
from .array import (
    ArrayBufferStager,
    ArrayIOPreparer,
    _TileConsumer,
    _want_crc,
    array_nbytes,
)


def should_chunk(arr) -> bool:
    return (
        len(arr.shape) > 0
        and arr.shape[0] > 1
        and array_nbytes(arr) > get_max_chunk_size_bytes()
    )


def chunk_row_ranges(shape: List[int], dtype: str, max_chunk_bytes: int) -> List[Tuple[int, int]]:
    row_nbytes = max(tensor_nbytes(dtype, shape[1:]), 1)
    rows_per_chunk = max(1, max_chunk_bytes // row_nbytes)
    n_rows = shape[0]
    return [
        (r0, min(r0 + rows_per_chunk, n_rows))
        for r0 in range(0, n_rows, rows_per_chunk)
    ]


class ChunkedArrayIOPreparer:
    @staticmethod
    def prepare_write(
        storage_path: str,
        arr,
        replicated: bool = False,
        is_async_snapshot: bool = False,
        array_prepare_func=None,
        array_prepare_traced=None,
        prev_entry=None,
        record_dedup_hashes: bool = False,
        chunk_rows: Optional[int] = None,
        prev_chunks: Optional[dict] = None,
    ) -> Tuple[ChunkedTensorEntry, List[WriteReq]]:
        """``chunk_rows``/``prev_chunks`` are set by the tile-grain
        incremental route (io_preparer.prepare_write): chunks follow the
        previous snapshot's checksum-tile grid instead of the chunk-size
        knob, and each chunk dedups against the synthesized per-tile
        entry for its row range — so only changed tiles are written."""
        from .array import trace_array_prepare

        # Chunk geometry follows the TRANSFORMED dtype (a cast-on-save
        # changes bytes-per-row); the transform itself is applied
        # per-chunk at stage time (reference chunked_tensor.py:82-94).
        if array_prepare_traced is not None:
            dtype, shape = array_prepare_traced[0], list(array_prepare_traced[1])
        else:
            dtype, shape = trace_array_prepare(arr, array_prepare_func)
        # Incremental dedup: match chunks of the previous snapshot's entry
        # by (offsets, sizes) — a changed chunk-size knob between takes
        # shifts boundaries and conservatively misses.
        if prev_chunks is None:
            prev_chunks = {}
            if isinstance(prev_entry, ChunkedTensorEntry):
                prev_chunks = {
                    (tuple(c.offsets), tuple(c.sizes)): c.tensor
                    for c in prev_entry.chunks
                }
        if chunk_rows is not None:
            n_rows = shape[0]
            ranges = [
                (r0, min(r0 + chunk_rows, n_rows))
                for r0 in range(0, n_rows, chunk_rows)
            ]
        else:
            ranges = chunk_row_ranges(shape, dtype, get_max_chunk_size_bytes())
        chunks: List[Chunk] = []
        write_reqs: List[WriteReq] = []
        ndim = len(shape)
        for r0, r1 in ranges:
            # Lazy device-side slice; DtoH happens at staging time.
            sub = arr[r0:r1]
            location = f"{storage_path}_{r0}_0"
            offsets = [r0] + [0] * (ndim - 1)
            sizes = [r1 - r0] + shape[1:]
            tensor_entry = TensorEntry(
                location=location,
                serializer=Serializer.BUFFER_PROTOCOL.value,
                dtype=dtype,
                shape=[r1 - r0] + shape[1:],
                replicated=replicated,
            )
            chunks.append(
                Chunk(offsets=offsets, sizes=sizes, tensor=tensor_entry)
            )
            write_reqs.append(
                WriteReq(
                    path=location,
                    buffer_stager=ArrayBufferStager(
                        sub,
                        is_async_snapshot,
                        entry=tensor_entry,
                        array_prepare_func=array_prepare_func,
                        dedup_entry=prev_chunks.get(
                            (tuple(offsets), tuple(sizes))
                        ),
                        record_dedup_hashes=record_dedup_hashes,
                    ),
                )
            )
        entry = ChunkedTensorEntry(
            dtype=dtype, shape=shape, chunks=chunks, replicated=replicated
        )
        return entry, write_reqs

    @staticmethod
    def prepare_read(
        entry: ChunkedTensorEntry,
        obj_out=None,
        buffer_size_limit_bytes: Optional[int] = None,
        logical_path: str = "",
    ) -> Tuple[List[ReadReq], Future]:
        """Chunks land in one preallocated host array via narrow views
        (reference chunked_tensor.py:65-126)."""
        fut: Future = Future()
        shape = entry.shape
        if isinstance(obj_out, np.ndarray) and (
            dtype_to_string(obj_out.dtype) == entry.dtype
            and list(obj_out.shape) == list(shape)
            and obj_out.flags.writeable
        ):
            host_out = obj_out
            in_place = True
        else:
            from .. import _native

            # Chunked entries are >512 MB by construction: fault the fresh
            # destination as hugepages (see _native.advise_hugepages).
            host_out = _native.empty_advised(shape, string_to_dtype(entry.dtype))
            in_place = False

        remaining = {"count": len(entry.chunks)}
        read_reqs: List[ReadReq] = []
        for chunk in entry.chunks:
            r0 = chunk.offsets[0]
            r1 = r0 + chunk.sizes[0]
            tensor_entry = chunk.tensor
            if tensor_entry.codec:
                # Compressed chunk: its own standalone compressed blob
                # — read the stored tiles, verify the chunk checksum
                # (over the stored bytes), fused-decompress into the
                # chunk's rows. Shares the array-wide remaining/fut
                # bookkeeping with the plain-chunk consumers.
                read_reqs.append(
                    _compressed_chunk_read_req(
                        tensor_entry,
                        host_out,
                        r0,
                        r1,
                        remaining,
                        fut,
                        obj_out,
                        in_place,
                        logical_path,
                    )
                )
                continue
            byte_range = (
                tuple(tensor_entry.byte_range)
                if tensor_entry.byte_range is not None
                else None
            )
            consumer = _TileConsumer(
                # _TileConsumer tiles over rows of `shape`; a chunk is
                # exactly a row range, so it is reused as-is.
                _chunk_as_full_entry(entry, chunk),
                host_out,
                r0,
                r1,
                remaining,
                fut,
                obj_out,
                in_place,
                # Each chunk read covers one complete stored blob,
                # so the chunk's whole-blob checksum is verifiable.
                blob_checksum=tensor_entry.checksum,
                blob_location=(
                    f"{logical_path or tensor_entry.location} "
                    f"(chunk @ row {r0})"
                ),
            )
            read_reqs.append(
                ReadReq(
                    path=tensor_entry.location,
                    byte_range=byte_range,
                    buffer_consumer=consumer,
                    into=consumer.into_mv,
                    want_crc=consumer.into_mv is not None
                    and _want_crc(tensor_entry),
                    # A raw chunk written whole is exactly its rows' bytes.
                    expected_nbytes=(
                        tensor_nbytes(tensor_entry.dtype, tensor_entry.shape)
                        if byte_range is None
                        else None
                    ),
                    logical_path=logical_path,
                )
            )
        return read_reqs, fut


def _compressed_chunk_read_req(
    tensor_entry: TensorEntry,
    host_out,
    r0: int,
    r1: int,
    remaining: dict,
    fut,
    obj_out,
    in_place: bool,
    logical_path: str,
) -> ReadReq:
    from ..knobs import is_checksum_disabled
    from .array import _CompressedConsumer, array_as_memoryview

    sizes = [int(s) for s in (tensor_entry.comp_tile_sizes or [])]
    raw_nbytes = tensor_entry.uncompressed_nbytes or tensor_nbytes(
        tensor_entry.dtype, tensor_entry.shape
    )
    n_rows = tensor_entry.shape[0] if tensor_entry.shape else 0
    row_nbytes = raw_nbytes // n_rows if n_rows else 0
    tile_raw = (
        (tensor_entry.tile_rows or 0) * row_nbytes
        if tensor_entry.tile_rows
        else raw_nbytes
    )
    from ..compress import check_tile_coverage

    check_tile_coverage(
        tensor_entry.location, len(sizes), raw_nbytes, tile_raw
    )
    row_slice = host_out[r0:r1]
    dest_mv = array_as_memoryview(row_slice)
    expected = (
        tensor_entry.checksum if not is_checksum_disabled() else None
    )
    consumer = _CompressedConsumer(
        entry=tensor_entry,
        dest_slice=dest_mv if not dest_mv.readonly else None,
        comp_sizes=sizes,
        tile_raw=tile_raw,
        raw_len=raw_nbytes,
        remaining=remaining,
        fut=fut,
        host_out=host_out,
        obj_out=obj_out,
        in_place=in_place,
        expected_checksum=expected,
        location=(
            f"{logical_path or tensor_entry.location} (chunk @ row {r0})"
        ),
    )
    return ReadReq(
        path=tensor_entry.location,
        byte_range=(0, sum(sizes)),
        buffer_consumer=consumer,
        want_crc=expected is not None,
        logical_path=logical_path,
    )


def tile_prev_map(
    prev_entry, dtype: str, shape: List[int]
) -> Optional[Tuple[int, dict]]:
    """Per-tile view of a previous snapshot's entry for tile-grain
    incremental dedup: ``(grid_rows, {(offsets, sizes): TensorEntry})``
    with one synthesized entry per checksum tile — its byte range within
    the previous blob, its recorded tile CRC, and its 64-bit tile dedup
    hash — or None when tile-grain dedup is not possible (mismatched
    identity, no tile checksums, no dedup hashes, or an irregular grid).

    Accepts a dense ``TensorEntry`` carrying ``tile_checksums`` +
    ``tile_dedup_hashes``, or a ``ChunkedTensorEntry`` produced by a
    previous tile-grain take (uniform tile-sized chunks, each carrying
    its own checksum + dedup_hash) — so incremental chains keep
    dedup'ing tile-grain after the first increment changes the entry's
    geometry. Every skip decision this map backs compares BOTH a 32-bit
    CRC and a 64-bit hash per tile (see dedup_entries_match)."""
    serializer = Serializer.BUFFER_PROTOCOL.value
    if (
        isinstance(prev_entry, TensorEntry)
        and prev_entry.serializer == serializer
        and prev_entry.dtype == dtype
        and list(prev_entry.shape) == list(shape)
        and prev_entry.tile_rows
        and prev_entry.tile_checksums
        and prev_entry.tile_dedup_hashes
        and len(prev_entry.tile_checksums) == len(prev_entry.tile_dedup_hashes)
        # Compressed bases: tile hashes are over STORED bytes at
        # compressed offsets — per-tile byte_range references into the
        # raw layout would be wrong. Dedup against a compressed base
        # stays whole-blob (dedup_entries_match compares codec+layout).
        and not prev_entry.codec
    ):
        t = prev_entry.tile_rows
        n_rows = shape[0]
        row_nbytes = tensor_nbytes(dtype, shape[1:]) if len(shape) > 1 else tensor_nbytes(dtype, [1])
        base = prev_entry.byte_range[0] if prev_entry.byte_range else 0
        ndim = len(shape)
        out = {}
        for i, (crc, dh) in enumerate(
            zip(prev_entry.tile_checksums, prev_entry.tile_dedup_hashes)
        ):
            r0, r1 = i * t, min((i + 1) * t, n_rows)
            offsets = tuple([r0] + [0] * (ndim - 1))
            sizes = tuple([r1 - r0] + list(shape[1:]))
            out[(offsets, sizes)] = TensorEntry(
                location=prev_entry.location,
                serializer=serializer,
                dtype=dtype,
                shape=list(sizes),
                replicated=False,
                byte_range=[base + r0 * row_nbytes, base + r1 * row_nbytes],
                checksum=crc,
                dedup_hash=dh,
            )
        return t, out
    if (
        isinstance(prev_entry, ChunkedTensorEntry)
        and prev_entry.dtype == dtype
        and list(prev_entry.shape) == list(shape)
        and prev_entry.chunks
    ):
        chunks = sorted(prev_entry.chunks, key=lambda c: c.offsets[0])
        t = chunks[0].sizes[0]
        n_rows = shape[0]
        out = {}
        expect_r0 = 0
        for i, c in enumerate(chunks):
            r0 = c.offsets[0]
            r1 = r0 + c.sizes[0]
            last = i == len(chunks) - 1
            if (
                r0 != expect_r0
                or (not last and c.sizes[0] != t)
                or (last and r1 != n_rows)
                or any(o != 0 for o in c.offsets[1:])
                or list(c.sizes[1:]) != list(shape[1:])
                or c.tensor.serializer != serializer
                or c.tensor.checksum is None
                or c.tensor.dedup_hash is None
                or c.tensor.tile_rows  # oversized chunk: grid not tile-sized
                or c.tensor.codec  # compressed chunk: blob-grain dedup only
            ):
                return None
            out[(tuple(c.offsets), tuple(c.sizes))] = c.tensor
            expect_r0 = r1
        if expect_r0 != n_rows or len(out) < 2:
            return None
        return t, out
    return None


def _chunk_as_full_entry(entry: ChunkedTensorEntry, chunk: Chunk) -> TensorEntry:
    return TensorEntry(
        location=chunk.tensor.location,
        serializer=chunk.tensor.serializer,
        dtype=entry.dtype,
        shape=entry.shape,
        replicated=entry.replicated,
        byte_range=chunk.tensor.byte_range,
    )
