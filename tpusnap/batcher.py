"""Request batcher: coalesce small writes into slabs; merge adjacent
byte-ranged reads into spanning reads.

Counterpart of /root/reference/torchsnapshot/batcher.py:48-474. Small
(< slab threshold) buffer-protocol array writes are packed into
uuid-named slab objects under ``batched/``; each member's TensorEntry is
rewritten in place to point at ``(slab_location, byte_range)``. Cloud
object stores charge per request and throttle request rates, so slab
packing is what makes thousands-of-small-parameters models fast on
S3/GCS. On read, byte-ranged requests against the same location are
merged into one spanning read and sliced back out.
"""

from __future__ import annotations

import asyncio
import logging
import uuid
from concurrent.futures import Executor
from typing import Dict, List, Optional, Tuple

import numpy as np

from . import telemetry
from .io_types import (
    BufferConsumer,
    BufferStager,
    BufferType,
    ReadReq,
    WriteReq,
    stager_aliases_caller_memory,
)
from .io_preparers.array import ArrayBufferStager, DonatedBeforeStagedError
from .knobs import (
    get_slab_size_threshold_bytes,
    is_batching_disabled,
    is_device_batching_disabled,
)
from .manifest import ChunkedTensorEntry, Entry, TensorEntry

logger = logging.getLogger(__name__)

# Bounds XLA compile time of the per-composition device pack program.
_MAX_DEVICE_SLAB_MEMBERS = 256

# A leaf whose staging cost reaches this is no slab member. A slab pays
# when it replaces many objects and transfers; at the default capacity
# members of 16 MiB or more make a slab of at most eight, which saves at
# most seven blobs' fixed cost on the parallel I/O threads for one pack,
# a second crossing of the bus and one hash of the whole slab on the one
# staging thread. Such a leaf's own DMA and its own blob are already at
# bandwidth: its prefetched host copy is the staged buffer and its hash
# is deferred to the write path, as for a leaf over the threshold.
# Why 16 MiB: no leaf of any benchmark cell lies between 9.2 MB
# (attention matrices) and 60 MiB (expert banks), and 8, 16 and 32 MiB
# read the same layout and the same time to durable on the chip (PR 29).
_MAX_SLAB_MEMBER_BYTES = 16 * 1024 * 1024


def _batchable_tensor_entries(entries: List[Entry]) -> Dict[str, TensorEntry]:
    """location → TensorEntry for every dense tensor blob (incl. chunks)."""
    out: Dict[str, TensorEntry] = {}
    for entry in entries:
        if isinstance(entry, TensorEntry):
            out[entry.location] = entry
        elif isinstance(entry, ChunkedTensorEntry):
            for chunk in entry.chunks:
                out[chunk.tensor.location] = chunk.tensor
    return out


def _start_members_dtoh(members) -> int:
    """A slab's ``start_dtoh``: its members' copies. The device slab's
    are started too, and thrown away when the pack succeeds (ROADMAP
    S2 (b)): the host fallback fetches them, and the count of a take's
    crossings holds the waste (``dtoh_bytes_per_state_byte``)."""
    return sum(s.start_dtoh() for _, _, s in members)


def _note_members(members) -> None:
    """Tell each member's stager that a slab carries it: its bytes cross
    inside the slab, so a large member is not copied on the chip for a
    crossing of its own (``ArrayBufferStager._crosses_owned``)."""
    for _, _, s in members:
        if isinstance(s, ArrayBufferStager):
            s.in_slab = True


def _any_member_aliases(members) -> bool:
    """A slab counts towards an async take's blocked window when any
    member's bytes may be written in place by the caller (see
    ``BufferStager.aliases_caller_memory``)."""
    return any(stager_aliases_caller_memory(s) for _, _, s in members)


class BatchedBufferStager(BufferStager):
    """Stages all members concurrently into one contiguous bytearray
    (reference BatchedBufferStager, batcher.py:48-98).

    Members carry their own incremental-dedup state: a member whose
    stager reports SKIP_WRITE (bytes match the base snapshot — its entry
    already re-pointed at the base slab's byte range) is EXCLUDED from
    the new slab, and the remaining members are compacted (entries'
    byte ranges reassigned). A fully-deduped slab skips its write
    entirely. Small states therefore stop rewriting 100% on every
    incremental take."""

    def __init__(self, members: List[Tuple[int, int, BufferStager]]) -> None:
        # members: [(offset, nbytes, stager)]
        self.members = members
        self.total = sum(n for _, n, _ in members)
        # Whether a member handed over the caller's live bytes under
        # copy-on-write (``io_types.stager_went_cow``).
        self.took_cow_members = False
        _note_members(members)

    async def stage_buffer(self, executor: Optional[Executor] = None) -> BufferType:
        from . import _native
        from .io_types import SKIP_WRITE

        # Aligned so the O_DIRECT writer pwrites straight from the slab.
        # Full-size upfront: members stream into their original offsets
        # as they land (each member's buffer is released immediately —
        # peak memory stays one slab + one member, matching
        # get_staging_cost_bytes); dedup'd members leave holes that one
        # in-place compaction pass closes at the end.
        slab = _native.aligned_empty(self.total)
        skipped = [False] * len(self.members)

        async def fill(i: int, offset: int, nbytes: int, stager: BufferStager) -> None:
            buf = await stager.stage_buffer(executor)
            if buf is SKIP_WRITE:
                skipped[i] = True  # member dedup'd against the base
                return
            mv = memoryview(buf).cast("B")
            if mv.nbytes != nbytes:
                raise RuntimeError(
                    f"Batched member staged {mv.nbytes} bytes, expected {nbytes}"
                )
            slab[offset : offset + nbytes] = np.frombuffer(mv, dtype=np.uint8)
            del mv
            if getattr(stager, "cow_pending", False):
                # COW members return LIVE bytes; the slab copy above is
                # their effective clone. The write pipeline only checks
                # cow_pending on the top-level (slab) stager, so verify
                # HERE — against the private slab copy, immediately —
                # that the bytes still match the checksum recorded from
                # the live array: a mutation between the hash pass and
                # this copy fails the take loudly instead of committing
                # a blob whose checksum mismatches its bytes.
                stager.verify_cow_after_write(slab[offset : offset + nbytes])
                stager.cow_pending = False
                self.took_cow_members = True
            from ._staging_pool import release

            release(buf)  # async member clones reuse warm pages next take

        await asyncio.gather(
            *(fill(i, o, n, s) for i, (o, n, s) in enumerate(self.members))
        )
        if not any(skipped):
            return slab
        # Compact in place around the dedup'd members (memmove — source
        # and destination overlap when moving left; numpy slice
        # assignment does not guarantee overlap safety) and return a
        # view of the kept prefix — no second allocation.
        import ctypes

        new_offset = 0
        for i, (offset, nbytes, stager) in enumerate(self.members):
            if skipped[i]:
                continue
            if new_offset != offset:
                ctypes.memmove(
                    slab.ctypes.data + new_offset,
                    slab.ctypes.data + offset,
                    nbytes,
                )
            entry = getattr(stager, "entry", None)
            if entry is not None:
                entry.byte_range = [new_offset, new_offset + nbytes]
            new_offset += nbytes
        if new_offset == 0:
            return SKIP_WRITE
        return slab[:new_offset]

    def start_dtoh(self) -> int:
        return _start_members_dtoh(self.members)

    def aliases_caller_memory(self) -> bool:
        return _any_member_aliases(self.members)

    def get_staging_cost_bytes(self) -> int:
        # The slab plus transiently one member's own staging cost; the
        # members' buffers are views/DMA targets released as they land.
        return self.total + max((s.get_staging_cost_bytes() for _, _, s in self.members), default=0)

    def get_planned_bytes(self) -> int:
        # The slab payload itself — members stream through transient
        # buffers that never count toward written bytes.
        return self.total


class DeviceBatchedBufferStager(BufferStager):
    """Packs same-device array members into one ``uint8`` buffer *on
    device* (XLA bitcast + fused concatenation), then performs a single
    device→host DMA for the whole slab.

    TPU-native counterpart of the reference's GPUBatchedBufferStager
    (batcher.py:101-159), which packs CUDA tensors into a byte tensor
    and issues one DtoH copy. One large DMA amortizes per-transfer
    dispatch overhead that thousands of small-parameter copies would
    otherwise pay. Falls back to the host-side ``BatchedBufferStager``
    on any failure (the reference falls back on CUDA OOM).

    The packed slab is a fresh XLA computation result, so its host copy
    can never alias live training state — async snapshots need no
    defensive clone here.

    Cost model: the pack program is jit-compiled once per slab
    *composition* (shapes/dtypes) and cached for the process — free for
    the steady-state checkpoint loop, a one-time cost on the first take.
    Slabs are capped at ``_MAX_DEVICE_SLAB_MEMBERS`` members to bound
    that compile time. ``TPUSNAP_DISABLE_DEVICE_BATCHING=1`` opts out
    (e.g. when device→host bandwidth, not per-transfer dispatch, is the
    bottleneck).
    """

    def __init__(self, members: List[Tuple[int, int, ArrayBufferStager]]) -> None:
        self.members = members
        self.total = sum(n for _, n, _ in members)
        # The host fallback's answer (``BatchedBufferStager``); a slab
        # packed on the device took no live bytes.
        self.took_cow_members = False
        _note_members(members)

    async def stage_buffer(self, executor: Optional[Executor] = None) -> BufferType:
        try:
            if executor is not None:
                return await telemetry.run_handoff(
                    executor, "stage", self._stage_blocking
                )
            return self._stage_blocking()
        except DonatedBeforeStagedError:
            raise  # the host path would only find the same array deleted
        except Exception as e:
            # Counted as well as logged, so a run that expects the
            # device path (chip_smoke.py) can assert it saw none.
            telemetry.incr("batcher.device_pack_fallbacks")
            logger.warning(
                "device slab packing failed (%s); falling back to host packing", e
            )
            on_host = BatchedBufferStager(list(self.members))
            slab = await on_host.stage_buffer(executor)
            self.took_cow_members = on_host.took_cow_members
            return slab

    def _stage_blocking(self) -> BufferType:
        from .knobs import is_checksum_disabled

        attrs = {"bytes": self.total, "slab_members": len(self.members)}
        # The pack alone: the program's dispatch until the packed buffer
        # is ready on the device, before any byte of it is fetched.
        for _, _, s in self.members:
            s.raise_if_donated()
        with telemetry.span("slab.pack", **attrs):
            packed = _pack_on_device(tuple(s.arr for _, _, s in self.members))
            packed.block_until_ready()
        telemetry.incr("batcher.device_slabs")
        telemetry.incr("batcher.device_slab_bytes", self.total)
        # A slab is fetched in this one blocking call, so here `dtoh` IS
        # the transfer, and `dtoh.transfer` is the same interval under the
        # name that the prefetched leaves' real transfers go by.
        with telemetry.span("dtoh.transfer", **attrs), telemetry.span("dtoh", **attrs):
            host = np.asarray(packed).view(np.uint8)  # the single DtoH DMA
        if host.nbytes != self.total:
            raise RuntimeError(
                f"device-packed slab is {host.nbytes} bytes, expected {self.total}"
            )
        if is_checksum_disabled():
            return host
        # The members' own stagers are bypassed by the device-side pack,
        # so record their checksums/dedup hashes from the slab slices
        # here — the same _record_checksums the host path runs, so both
        # paths produce identical manifests. Members matching their base
        # entry (incremental dedup) are dropped and the slab compacted,
        # exactly like BatchedBufferStager. (The packed slab is a fresh
        # XLA result, so member bytes are stable — no clone needed.)
        from .io_preparers.array import _record_checksums, dedup_entries_match
        from .io_types import SKIP_WRITE

        keep: List[Tuple[int, int]] = []  # (old_offset, nbytes)
        keep_stagers: List[ArrayBufferStager] = []
        for offset, nbytes, stager in self.members:
            if stager.entry is None:
                keep.append((offset, nbytes))
                keep_stagers.append(stager)
                continue
            mv = memoryview(host[offset : offset + nbytes])
            dedup = getattr(stager, "dedup_entry", None)
            _record_checksums(
                stager.entry, mv, getattr(stager, "record_dedup_hashes", False)
            )
            if dedup is not None and dedup_entries_match(stager.entry, dedup):
                stager.entry.location = dedup.location
                stager.entry.byte_range = (
                    list(dedup.byte_range)
                    if dedup.byte_range is not None
                    else None
                )
                continue
            keep.append((offset, nbytes))
            keep_stagers.append(stager)
        if not keep:
            return SKIP_WRITE
        if len(keep) == len(self.members):
            return host
        from . import _native

        # Aligned so the O_DIRECT writer pwrites straight from it (the
        # host-path slab is allocated the same way).
        out = _native.aligned_empty(sum(n for _, n in keep))
        new_offset = 0
        for (old_offset, nbytes), stager in zip(keep, keep_stagers):
            out[new_offset : new_offset + nbytes] = host[
                old_offset : old_offset + nbytes
            ]
            if stager.entry is not None:
                stager.entry.byte_range = [new_offset, new_offset + nbytes]
            new_offset += nbytes
        return out

    def start_dtoh(self) -> int:
        return _start_members_dtoh(self.members)

    def aliases_caller_memory(self) -> bool:
        # The packed slab aliases nothing, but the host fallback stages
        # the members themselves: the slab answers as they do.
        return _any_member_aliases(self.members)

    def get_staging_cost_bytes(self) -> int:
        # Partial dedup holds the DMA'd slab AND the compacted copy at
        # once (the DMA result may alias XLA-owned memory, so unlike the
        # host path it cannot compact in place): budget 2x whenever a
        # member might dedup.
        if any(
            getattr(s, "dedup_entry", None) is not None
            for _, _, s in self.members
        ):
            return 2 * self.total
        return self.total

    def get_planned_bytes(self) -> int:
        # The slab payload — never the 2x dedup-compaction budget.
        return self.total


def _pack_on_device(arrs):
    """``_pack_members`` as one fused XLA program, jit-cached per slab
    composition."""
    return _ensure_pack_jit()(arrs)


def _pack_members(arrs):
    """The members' bytes, end to end, as one flat array: ``uint32`` when
    every member is 4-byte elements, else ``uint8``. The host reads either
    as the same bytes (both sides are little-endian).

    Words, because a TPU lays 8-bit arrays out in tiles that pad a minor
    dimension of 4 to 128: the byte-wise program of a 60 MiB float32 member
    holds 2 GB of temporaries and takes the compiler 30-80 s (measured for
    a described v5e); the word-wise one holds none and compiles in under a
    second."""
    import jax
    import jax.numpy as jnp

    words = all(a.dtype.itemsize == 4 for a in arrs)
    flat = []
    for a in arrs:
        if words:
            f = jax.lax.bitcast_convert_type(a, jnp.uint32)
        elif a.dtype == jnp.bool_:
            f = a.astype(jnp.uint8)  # bool is 1 byte, values 0/1
        else:
            f = jax.lax.bitcast_convert_type(a, jnp.uint8)
        flat.append(f.reshape(-1))
    return jnp.concatenate(flat) if len(flat) > 1 else flat[0]


_pack_jit = None


def _ensure_pack_jit():
    global _pack_jit
    if _pack_jit is None:
        import jax

        _pack_jit = jax.jit(_pack_members)
    return _pack_jit


def _device_group_key(stager: BufferStager) -> Optional[str]:
    """Same-device jax.Array members eligible for device packing share a
    key; ``None`` → host packing."""
    if is_device_batching_disabled() or not isinstance(stager, ArrayBufferStager):
        return None
    if stager.array_prepare_func is not None:
        # The device pack bitcasts the ORIGINAL arrays; a save-time
        # transform must run through the member stagers (host packing
        # calls them; the device path would silently skip it).
        return None
    import jax
    import numpy as np

    arr = stager.arr
    if not isinstance(arr, jax.Array):
        return None
    try:
        from .host_offload import is_offloaded_to_host

        if is_offloaded_to_host(arr):
            # Genuinely offloaded (host kind distinct from the device's
            # default memory): packing on device would round-trip the
            # bytes through a DMA for nothing. Default-placed arrays on
            # backends whose default memory IS a host kind (CPU) still
            # device-pack — there the pack is a fused concat, no DMA.
            return None
        devices = arr.devices()
    except Exception:
        return None
    if len(devices) != 1:
        return None
    if np.dtype(arr.dtype).kind == "c":
        return None  # complex: no u8 bitcast path
    return str(next(iter(devices)))


def batch_write_requests(
    entries: List[Entry], write_reqs: List[WriteReq]
) -> Tuple[List[Entry], List[WriteReq]]:
    """Pack small array writes into slabs, rewriting entries in place
    (reference batch_write_requests, batcher.py:201-352)."""
    # The threshold is a slab's capacity; a member is bounded by the
    # smaller of it and the member size.
    threshold = get_slab_size_threshold_bytes()
    if is_batching_disabled():
        return entries, write_reqs
    member_limit = min(threshold, _MAX_SLAB_MEMBER_BYTES)

    entry_by_location = _batchable_tensor_entries(entries)
    candidates: List[WriteReq] = []
    passthrough: List[WriteReq] = []
    for wr in write_reqs:
        stager = wr.buffer_stager
        if not (isinstance(stager, ArrayBufferStager) and wr.path in entry_by_location):
            passthrough.append(wr)
            continue
        cost = stager.get_staging_cost_bytes()
        if cost < member_limit:
            candidates.append(wr)
            continue
        passthrough.append(wr)
        if cost < threshold:
            # Under a slab's capacity: kept whole by the member size alone.
            telemetry.incr("batcher.whole_leaves")
            telemetry.incr("batcher.whole_leaf_bytes", stager.get_planned_bytes())
    if len(candidates) < 2:
        return entries, write_reqs

    batched_reqs: List[WriteReq] = []
    slab_members: List[Tuple[int, int, BufferStager]] = []
    slab_entries: List[TensorEntry] = []
    slab_device: Optional[str] = None
    offset = 0

    def flush() -> None:
        nonlocal offset, slab_members, slab_entries
        if not slab_members:
            return
        if len(slab_members) == 1:
            # A slab of one is pointless; leave the request as-is.
            passthrough.append(
                WriteReq(path=slab_entries[0].location, buffer_stager=slab_members[0][2])
            )
        else:
            location = f"batched/{uuid.uuid4().hex}"
            for (member_offset, nbytes, stager), tensor_entry in zip(
                slab_members, slab_entries
            ):
                tensor_entry.location = location
                tensor_entry.byte_range = [member_offset, member_offset + nbytes]
                # Members keep their dedup state: one that matches its
                # base entry skips (its entry re-pointed at the base
                # slab's byte range) and the stager compacts the slab
                # around it at stage time.
            stager_cls = (
                DeviceBatchedBufferStager
                if slab_device is not None
                else BatchedBufferStager
            )
            batched_reqs.append(
                WriteReq(
                    path=location,
                    buffer_stager=stager_cls(list(slab_members)),
                )
            )
        offset = 0
        slab_members = []
        slab_entries = []

    from .serialization import tensor_nbytes

    # Stable-sort by device group so same-device members land in the
    # same slab and take the single-DMA device packing path.
    keyed = [(_device_group_key(wr.buffer_stager), wr) for wr in candidates]
    keyed.sort(key=lambda kv: kv[0] or "")
    for device_key, wr in keyed:
        tensor_entry = entry_by_location[wr.path]
        nbytes = tensor_nbytes(tensor_entry.dtype, tensor_entry.shape)
        if slab_members and (
            offset + nbytes > threshold
            or device_key != slab_device
            or (device_key is not None and len(slab_members) >= _MAX_DEVICE_SLAB_MEMBERS)
        ):
            flush()
        slab_device = device_key
        slab_members.append((offset, nbytes, wr.buffer_stager))
        slab_entries.append(tensor_entry)
        offset += nbytes
    flush()

    return entries, passthrough + batched_reqs


class _SpanningConsumer(BufferConsumer):
    """Feeds slices of one spanning read to the member consumers
    (reference read-side merge, batcher.py:384-474)."""

    def __init__(
        self, span_start: int, members: List[Tuple[Tuple[int, int], BufferConsumer]]
    ) -> None:
        self.span_start = span_start
        self.members = members

    async def consume_buffer(
        self, buf: BufferType, executor: Optional[Executor] = None
    ) -> None:
        mv = memoryview(buf).cast("B")
        for (start, end), consumer in self.members:
            await consumer.consume_buffer(
                mv[start - self.span_start : end - self.span_start], executor
            )

    def get_consuming_cost_bytes(self) -> int:
        return sum(c.get_consuming_cost_bytes() for _, c in self.members)


def batch_read_requests(read_reqs: List[ReadReq]) -> List[ReadReq]:
    """Merge byte-ranged reads per location into one spanning read when the
    span is dense enough that one request beats many."""
    by_location: Dict[str, List[ReadReq]] = {}
    passthrough: List[ReadReq] = []
    for rr in read_reqs:
        if rr.byte_range is not None:
            by_location.setdefault(rr.path, []).append(rr)
        else:
            passthrough.append(rr)

    out = list(passthrough)
    for location, reqs in by_location.items():
        if len(reqs) == 1:
            out.extend(reqs)
            continue
        reqs.sort(key=lambda r: r.byte_range[0])
        span_start = reqs[0].byte_range[0]
        span_end = max(r.byte_range[1] for r in reqs)
        total = sum(r.byte_range[1] - r.byte_range[0] for r in reqs)
        if total < (span_end - span_start) * 0.5:
            # Sparse: spanning read would over-fetch badly; keep individual.
            out.extend(reqs)
            continue
        out.append(
            ReadReq(
                path=location,
                byte_range=(span_start, span_end),
                buffer_consumer=_SpanningConsumer(
                    span_start,
                    [(tuple(r.byte_range), r.buffer_consumer) for r in reqs],
                ),
                # Per-member attribution survives the merge: the access
                # ledger records each member's own leaf and range, not
                # the opaque spanning read.
                access_parts=[
                    (r.logical_path, r.byte_range[0], r.byte_range[1])
                    for r in reqs
                    if r.logical_path
                ]
                or None,
            )
        )
    return out
