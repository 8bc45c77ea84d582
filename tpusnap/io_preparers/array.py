"""Dense-array IO preparer — the inner loop of every snapshot.

TPU-native counterpart of /root/reference/torchsnapshot/io_preparers/tensor.py.
Where the reference stages with ``Tensor.to("cpu")`` in GIL-released
TorchScript (tensor.py:247-305,351-358), this preparer uses XLA's async
device→host DMA: ``jax.Array.copy_to_host_async()``. A stager starts no
copy when it is built; ``start_dtoh()`` does, once, and the write
scheduler calls it as it dispatches requests, a fixed depth ahead of the
thread that fetches (``_WriteScheduler._start_dtoh_ahead``): on the TPU
runtime a program dispatched after a copy waits behind it, so a state's
copies all started at once stand in front of the caller's next step,
while a step does run between two leaves' copies. The thread-pooled
``np.asarray`` in ``stage_buffer`` then waits out what is left of its
leaf's copy (numpy releases the GIL for the copy; the PJRT transfer
releases it too). Where the caller's steps may run beside the transfer, a
large accelerator leaf does not cross from the caller's own buffer:
``start_dtoh()`` has the runtime copy it on the chip (``_own_copy``: no
compiled program, the leaf's shape, type and layout) and copies that
buffer, which only tpusnap holds and which is let go once its bytes are
seen on the host (``_crosses_owned``; docs/design.md, "The owned
crossing": the steps beside a draining take lose a third less). A caller
that stands in the take or in ``wait_staged()`` runs no step the copy could
protect and would only wait for it, and a device that has not the room
free keeps it for the caller: the leaf then crosses as it lies. Such a
leaf's host value is kept by the runtime on the caller's own array, and
staging stages that value (unless it has to turn or compress it): the end
of its write frees nothing, so the write scheduler does not charge it to
its staging budget (``stages_callers_host_value``).

Differences by design:
- JAX arrays are immutable, so the reference's in-place load
  (tensor.py:101,188-196) becomes: build a zero-copy numpy view over the
  read buffer and ``jax.device_put`` it with the restore target's
  sharding; for numpy targets we np.copyto in place.
- The async-snapshot defensive clone (tensor.py:281-305) is a host-side
  ``bytes()`` copy: on CPU backends ``np.asarray(jax_array)`` may alias
  the device buffer, which a donated update could overwrite.
"""

from __future__ import annotations

import logging
import math
import threading
import time
import weakref
from concurrent.futures import Executor
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from .. import telemetry
from ..host_offload import is_host_resident
from ..io_types import (
    BufferConsumer,
    BufferStager,
    BufferType,
    Future,
    ReadReq,
    WriteReq,
)
from ..manifest import TensorEntry
from ..serialization import (
    RELAYOUT_MIN_BYTES,
    Serializer,
    array_as_memoryview,
    array_from_memoryview,
    c_order_copy_into,
    dtype_to_string,
    tensor_nbytes,
)

logger = logging.getLogger(__name__)

ArrayLike = object  # jax.Array | np.ndarray


def array_nbytes(arr: ArrayLike) -> int:
    return int(np.prod(arr.shape)) * np.dtype(arr.dtype).itemsize if arr.shape else np.dtype(arr.dtype).itemsize


def is_supported_array_dtype(arr: ArrayLike) -> bool:
    try:
        dtype_to_string(arr.dtype)
        return True
    except ValueError:
        return False


def enqueue_dtoh(arr: ArrayLike) -> Optional[float]:
    """Start the device→host DMA ahead of the fetch, and return the
    ``time.monotonic()`` reading of the call (None where no transfer
    was started): the start of the leaf's ``dtoh.transfer``.

    Host-offloaded arrays (host_offload.py, the UVM analog) skip the
    enqueue: their buffers already live in host memory, so staging is a
    plain view — the reference's uvm_to_cpu shortcut
    (io_preparers/tensor.py:257-259)."""
    if not isinstance(arr, jax.Array) or is_host_resident(arr):
        return None
    started = time.monotonic()
    try:
        arr.copy_to_host_async()
    except Exception as e:
        # Staging still works (np.asarray blocks on the transfer), but
        # the prefetch the design relies on did not happen.
        logger.warning(
            "copy_to_host_async failed on %s (%s: %s); device→host "
            "transfer will not be prefetched",
            next(iter(arr.devices())).platform,
            type(e).__name__,
            e,
        )
        return None
    # Counted at enqueue: a member the batcher later packs on device is
    # transferred a second time inside the slab, and only the two counts
    # together show that.
    telemetry.incr("dtoh.enqueued_bytes", array_nbytes(arr))
    return started


def _own_copy(arr: jax.Array) -> jax.Array:
    """A second buffer on ``arr``'s own device holding its elements, in its
    shape, type and layout; the caller gets the only reference to it. Made
    by the runtime (``may_alias=False`` is a copy even onto the device the
    array lies on), not by a compiled program: no shape costs a
    compilation or a cache lookup."""
    return jax.device_put(arr, may_alias=False)


def _lies_on_one_accelerator(arr: ArrayLike) -> bool:
    """Whether ``arr`` is a whole array in one accelerator's own memory,
    whose ``np.asarray`` is a transfer the runtime lands on the host.
    Never a CPU backend's array, whose ``np.asarray`` is a view: nothing
    crosses, and a copy would only be one more. Asked of the backend's
    own probe, not of its name."""
    return (
        isinstance(arr, jax.Array)
        and len(arr.devices()) == 1
        and not _may_alias_live_memory(arr, None)
    )


def _reaches_host_turned(arr: jax.Array) -> bool:
    """Whether ``np.asarray(arr)`` will come in another order than C, for
    ``_relayout`` to turn into a buffer of tpusnap's own: read off the
    device's own layout of the leaf (a TPU lays a leaf whose minor
    dimension is no multiple of 128 out with its dimensions swapped, and
    the host value keeps that order), before a byte has crossed. A
    runtime that cannot be asked gets the answer that is charged."""
    if arr.ndim < 2 or array_nbytes(arr) < RELAYOUT_MIN_BYTES:
        return False
    try:
        return tuple(arr.format.layout.major_to_minor) != tuple(range(arr.ndim))
    except Exception:
        return True


def _is_out_of_device_memory(e: BaseException) -> bool:
    return isinstance(e, jax.errors.JaxRuntimeError) and "RESOURCE_EXHAUSTED" in str(e)


# The share of a device's memory that owned copies never reach into: what
# the allocator's fragments and a step a little larger than any before it
# may need (a sixteenth: 1 GiB of a v5e's 16).
_OWNED_CLEAR_SHARE = 16
# Bytes of owned copies alive on each device, every take's together: a
# copy counts from its making until the last reference to it dies.
_owned_live: Dict[Any, int] = {}
_owned_live_lock = threading.Lock()


def _device_free_bytes(device) -> Optional[int]:
    """What of ``device``'s memory no allocation of this process has ever
    reached (its limit less the allocator's peak: the caller's state, its
    steps' temporaries and tpusnap's own earlier copies), less the share
    kept clear; None where the backend reports no such numbers."""
    stats = device.memory_stats()
    try:
        limit = int(stats["bytes_limit"])
        return limit - int(stats["peak_bytes_in_use"]) - limit // _OWNED_CLEAR_SHARE
    except (TypeError, KeyError):
        return None


def _note_owned(device, nbytes: int) -> None:
    with _owned_live_lock:
        _owned_live[device] = _owned_live.get(device, 0) + nbytes


def _has_room_for_owned(device, nbytes: int) -> bool:
    """Whether a copy of ``nbytes`` may be made on ``device``: the caller's
    next step allocates its temporaries beside every copy alive, and must
    find what it found before the take. A copy that the runtime would
    make all the same could fail that step instead of itself. A device
    that cannot be asked has no room."""
    free = _device_free_bytes(device)
    with _owned_live_lock:
        live = _owned_live.get(device, 0)
    return free is not None and live + nbytes <= free


class ArrayBufferStager(BufferStager):
    def __init__(
        self,
        arr: ArrayLike,
        is_async_snapshot: bool = False,
        entry: Optional[TensorEntry] = None,
        array_prepare_func: Optional[Callable[[ArrayLike, bool], ArrayLike]] = None,
        dedup_entry: Optional[TensorEntry] = None,
        record_dedup_hashes: bool = False,
        compressible: bool = True,
    ) -> None:
        self.arr = arr
        self.is_async_snapshot = is_async_snapshot
        # Fused tile compression (tpusnap.compress): the take's policy
        # sets ``compress_codec`` on eligible stagers after batching;
        # staging then runs the fused shuffle+LZ4+dual-hash pass and
        # the staged buffer IS the compressed blob. ``compressible``
        # is construction-time eligibility: sharded shards opt out
        # (their restore path reads arbitrary overlap sub-ranges,
        # impossible at compressed-tile grain).
        self.compressible = compressible
        self.compress_codec: Optional[str] = None
        # Per-take clone-staging override, armed by the take after
        # batching (delta micro-commits force defensive clones: their
        # free-running captures cannot rendezvous with the training
        # thread, so COW's write-time verify would fail every commit).
        self.force_clone = False
        # Manifest entry to annotate with the stage-time checksum. The
        # manifest is gathered after staging completes, so the value lands
        # in the committed metadata.
        self.entry = entry
        # Incremental snapshots: the previous snapshot's entry for this
        # blob, locations already rewritten relative to the NEW snapshot
        # root. If the staged bytes hash to the same checksums, the write
        # is skipped and ``entry`` adopts the previous blob's location.
        self.dedup_entry = dedup_entry
        # Incremental takes record 64-bit per-tile dedup hashes so the
        # NEXT increment can make tile-grain skip decisions with more
        # than 32 bits of evidence (small tile-less blobs record theirs
        # eagerly on every take — see _record_checksums).
        self.record_dedup_hashes = record_dedup_hashes
        # Set by the take AFTER batching (non-incremental takes, any
        # world size): skip hashing at stage time; the write pipeline
        # calls late_checksum with the staged buffer instead — the hash
        # pass moves off the staging window async_take blocks training
        # on and overlaps other requests' disk time. Multi-process
        # manifests gather by value at staging-complete, so the late
        # values reach the commit via the barrier's KV store
        # (snapshot._LateChecksums). Incremental dedup needs hashes at
        # stage time and never defers.
        self.defer_checksums = False
        # Copy-on-write staging (TPUSNAP_ASYNC_COW, the default): set by
        # _stage_blocking when it returns the LIVE host bytes instead of
        # a defensive clone. The write pipeline then calls
        # verify_cow_after_write once the storage write completes; a
        # checksum mismatch (the caller mutated the array mid-take)
        # fails the take instead of committing torn data.
        self.cow_pending = False
        # User save-time transform (dtype cast / quantize-on-save),
        # applied to the ORIGINAL array at stage time with tracing=False
        # (reference io_preparers/tensor.py:231-241).
        self.array_prepare_func = array_prepare_func
        # May the caller write this leaf's bytes in place once
        # async_take has returned? Asked once, of the array alone; a
        # sync take has no such moment and is not asked.
        self._aliases_caller_memory = (
            not is_async_snapshot or _may_alias_live_memory(arr, None)
        )
        # When the prefetch of this leaf's host copy was started, if it
        # was: by start_dtoh(), never here.
        self.dtoh_started: Optional[float] = None
        self._dtoh_asked = False
        # Set by a slab that takes this leaf as a member: its bytes cross
        # inside the slab (or are fetched by the slab's host fallback).
        self.in_slab = False
        # Cleared by the write scheduler before ``start_dtoh()`` where the
        # caller runs no step while this leaf crosses: it stands in the
        # take, or in ``wait_staged()`` (``_WriteScheduler._steps_may_run``).
        self.beside_steps = True
        # tpusnap's own copy of the leaf on the chip, from start_dtoh()
        # until its bytes are seen on the host (see _crosses_owned): what
        # copy_to_host_async() was called on.
        self._owned: Optional[jax.Array] = None
        # Set where the leaf's copy was started from the caller's own
        # buffer on an accelerator (it crosses as it lies): the host
        # value then lives on the caller's array
        # (``stages_callers_host_value``).
        self._as_it_lies = False
        # The leaf's host value is in another order than C and staging
        # turns it into a pooled buffer: foreseen from the device's
        # layout when the copy is started, set by staging where it
        # turned one that was not foreseen.
        self._turned = False

    def _prefetches(self) -> bool:
        """Whether ``start_dtoh`` has a copy to start: an accelerator's
        array staged as it is. A transform usually changes the bytes, so
        prefetching the untransformed array would be wasted DMA."""
        return self.array_prepare_func is None and not is_host_resident(self.arr)

    def _crosses_owned(self) -> bool:
        """Whether a leaf that ``_prefetches()`` crosses from a copy on
        the chip that tpusnap owns, and not from the caller's buffer,
        which its next step takes as an argument. Read off the array and
        the stager alone: a whole leaf in one accelerator's memory, not a
        slab's member, and large enough to matter (``RELAYOUT_MIN_BYTES``:
        below it a leaf is in a slab or small)."""
        return (
            not self.in_slab
            and array_nbytes(self.arr) >= RELAYOUT_MIN_BYTES
            and _lies_on_one_accelerator(self.arr)
        )

    def start_dtoh(self) -> int:
        """Start this leaf's copy to the host, once, and return the
        bytes under way (0 where there is no copy to start). Where the
        caller runs no step meanwhile (``beside_steps`` cleared), a leaf
        that would cross from an owned copy crosses as it lies, and
        ``dtoh.owned_waived`` counts it: the copy would protect nothing,
        and the transfer would wait for it."""
        if not self._dtoh_asked:
            self._dtoh_asked = True
            # A deleted array is staging's to report, by the leaf's name.
            if self._prefetches() and not self.arr.is_deleted():
                if self._crosses_owned():
                    if self.beside_steps:
                        self.dtoh_started = self._start_owned()
                    else:
                        telemetry.incr("dtoh.owned_waived")
                if self.dtoh_started is None:
                    self._start_as_it_lies()
        return array_nbytes(self.arr) if self.dtoh_started is not None else 0

    def _start_as_it_lies(self) -> None:
        """Start the transfer of the caller's own buffer. On an
        accelerator the runtime lands a host value and keeps it with the
        caller's array; staging stages that value unless it has to turn
        it."""
        self.dtoh_started = enqueue_dtoh(self.arr)
        if (
            self.dtoh_started is not None
            and not self.in_slab  # a member's bytes are staged by its slab
            and _lies_on_one_accelerator(self.arr)
        ):
            self._as_it_lies = True
            self._turned = _reaches_host_turned(self.arr)

    def _start_owned(self) -> Optional[float]:
        """Copy the leaf on the chip and start that copy's transfer;
        return when the transfer was started (``time.monotonic()``), or
        None where the chip has no room for the copy, by its own count
        before the copy or by the runtime's refusal: the leaf then
        crosses as it lies."""
        nbytes = array_nbytes(self.arr)
        device = next(iter(self.arr.devices()))
        if not _has_room_for_owned(device, nbytes):
            telemetry.incr("dtoh.owned_fallbacks")
            return None
        with telemetry.span("dtoh.own_copy", kind=telemetry.WORK, bytes=nbytes):
            try:
                owned = _own_copy(self.arr)
                _note_owned(device, nbytes)
                weakref.finalize(owned, _note_owned, device, -nbytes)
                # Where a leaf that crosses as it lies starts its own.
                started = time.monotonic()
                owned.copy_to_host_async()
            except RuntimeError as e:
                if not _is_out_of_device_memory(e):
                    self.raise_if_donated()  # deleted under the call
                    raise
                telemetry.incr("dtoh.owned_fallbacks")
                return None
        self._owned = owned
        telemetry.incr("dtoh.enqueued_bytes", nbytes)
        telemetry.incr("dtoh.owned_bytes", nbytes)
        telemetry.incr("dtoh.owned_leaves")
        return started

    def host_array(self) -> np.ndarray:
        """The leaf's bytes on the host: the one fetch of the one copy
        ``start_dtoh()`` started (JAX keeps the host value with the array
        it was fetched from, so a second call costs nothing), for staging
        and for the codec policy's sampler alike."""
        owned = self._owned
        if owned is not None:
            try:
                return np.asarray(owned)
            except jax.errors.JaxRuntimeError as e:
                if not _is_out_of_device_memory(e):
                    raise
                # The copy found no room on its way: the leaf crosses as
                # it lies, and that crossing is counted too.
                self._owned = None
                telemetry.incr("dtoh.owned_fallbacks")
                self._start_as_it_lies()
        return np.asarray(self.arr)

    def host_bytes_are_free(self) -> bool:
        """Whether ``host_array()`` yields the bytes staging
        will stage and runs no device operation of its own: a numpy
        leaf, or an accelerator's array with no ``array_prepare_func``
        (after ``start_dtoh()`` the call waits out that one copy, and JAX
        keeps the host value for ``_stage_blocking``'s own call). Never
        for a leaf behind an ``array_prepare_func``, whose staged bytes
        are another array's. The compress policy samples only such a
        leaf."""
        if self.array_prepare_func is not None:
            return False
        return isinstance(self.arr, np.ndarray) or self._prefetches()

    def aliases_caller_memory(self) -> bool:
        return self._aliases_caller_memory

    def stages_callers_host_value(self) -> bool:
        """See ``BufferStager``: an accelerator leaf that crosses as it
        lies (known once ``start_dtoh()`` has run) and is neither turned
        nor compressed. The caller cannot write that host value (its
        array is immutable; a donating step only drops it, and the staged
        view keeps it alive), so it is not cloned either."""
        return self._as_it_lies and not self._turned and self.compress_codec is None

    def raise_if_donated(self) -> None:
        """Fail the take if a step has donated (and so deleted) the
        source array before it was staged, by the leaf's name and with
        the way out, where JAX would say "Array has been deleted"."""
        if isinstance(self.arr, jax.Array) and self.arr.is_deleted():
            leaf = self.entry.location if self.entry is not None else "a leaf"
            raise DonatedBeforeStagedError(
                f"{leaf}: a step donated this array before it was staged: "
                "call PendingSnapshot.wait_staged() before a step that "
                "donates, or set TPUSNAP_ASYNC_STAGE_WINDOW_BYTES=0"
            )

    async def stage_buffer(self, executor: Optional[Executor] = None) -> BufferType:
        if executor is not None:
            return await telemetry.run_handoff(executor, "stage", self._stage_blocking)
        return self._stage_blocking()

    def _stage_blocking(self) -> BufferType:
        from ..knobs import is_checksum_disabled

        arr = self.arr
        self.raise_if_donated()
        if self.array_prepare_func is not None:
            arr = self.array_prepare_func(arr, False)  # tracing=False
            if self.entry is not None and (
                list(arr.shape) != list(self.entry.shape)
                or dtype_to_string(arr.dtype) != self.entry.dtype
            ):
                raise RuntimeError(
                    "_custom_array_prepare_func returned "
                    f"{arr.dtype}{list(arr.shape)} at stage time but "
                    f"{self.entry.dtype}{list(self.entry.shape)} was "
                    "recorded at prepare time — the transform must be "
                    "deterministic"
                )
        rec = telemetry.current()
        dtoh_t0 = (
            rec.now()
            if rec is not None and rec.enabled and not isinstance(arr, np.ndarray)
            else None
        )
        try:
            # DtoH (no-op if DMA already done)
            host = self.host_array() if arr is self.arr else np.asarray(arr)
        except RuntimeError:
            self.raise_if_donated()  # deleted under the call
            raise
        # The owned copy leaves the chip once its bytes are seen here.
        self._owned = None
        prefetched = arr is self.arr and self.dtoh_started is not None
        if not prefetched and not is_host_resident(arr):
            # No copy was under way: the fetch above was the transfer.
            # Only a leaf behind an array_prepare_func may count here.
            telemetry.incr("dtoh.cold_fetches")
        if dtoh_t0 is not None:
            # `dtoh` is this call alone. For a prefetched leaf that is
            # the residual wait for a copy the scheduler started ahead,
            # not the transfer; `dtoh.transfer` runs from the start of
            # the copy to here, where the host copy is SEEN ready: an
            # upper bound on the transfer (ready is observed, not
            # signalled, and a leaf staged late was ready long before).
            done = rec.now()
            started = self.dtoh_started - rec.t0 if prefetched else dtoh_t0
            rec.record_span(
                "dtoh", dtoh_t0, done - dtoh_t0, bytes=host.nbytes,
                kind=telemetry.WAIT if prefetched else telemetry.WORK,
            )
            rec.record_span(
                "dtoh.transfer", started, done - started, bytes=host.nbytes,
                kind=telemetry.WORK,
            )
        # A large host array in another order than C (a device array
        # whose minor dimension is no multiple of the tile reaches the
        # host with its dimensions swapped; a caller's Fortran-ordered
        # or transposed numpy leaf) is turned here, in parallel pieces
        # into a pooled buffer, and not by np.ascontiguousarray inside
        # array_as_memoryview. The staged bytes are that buffer's: they
        # alias nothing live, so nothing below clones or re-verifies,
        # and where no other buffer is staged the pool's own array is
        # returned, for the write pipeline to hand back.
        relaid = _relayout(host)
        if relaid is not None:
            self._turned = True
        mv = memoryview(relaid) if relaid is not None else array_as_memoryview(host)
        staged = relaid if relaid is not None else mv
        want_crc = self.entry is not None and not is_checksum_disabled()
        if self.compress_codec is not None and want_crc:
            # Fused tile compression: the staged buffer is the
            # compressed blob — fresh memory that never aliases the
            # live array, so async takes need neither the defensive
            # clone nor the COW write-time re-verify, and dedup (when
            # armed) compares hashes of the compressed bytes. Handles
            # its own dedup/skip decision.
            compressed = self._stage_compressed(mv)
            _release_clone_buffer(relaid)
            return compressed
        if want_crc and self.dedup_entry is not None:
            # Incremental dedup: hash first (the expected outcome is
            # "unchanged", where no clone and no write happen at all).
            # A skip decision needs MORE than 32 bits of evidence per
            # unit of skipped data (ADVICE r4: tile CRCs alone leave a
            # single-CRC channel when the change is confined to one
            # tile), so the 64-bit lane rides the SAME fused memory
            # pass as the CRCs: record_dedup_hashes is always True when
            # dedup_entry is set (incremental takes force it,
            # snapshot.py), which is what arms the 64-bit side of the
            # match below. A base without recorded hashes
            # conservatively rewrites (dedup_entries_match).
            from ..io_types import SKIP_WRITE

            _record_checksums(self.entry, mv, self.record_dedup_hashes)
            if dedup_entries_match(self.entry, self.dedup_entry):
                self.entry.location = self.dedup_entry.location
                self.entry.byte_range = (
                    list(self.dedup_entry.byte_range)
                    if self.dedup_entry.byte_range is not None
                    else None
                )
                _release_clone_buffer(relaid)
                return SKIP_WRITE
            clone = (
                relaid is None
                and self.is_async_snapshot
                and self._aliases_caller_memory
            )
            if clone:
                from ..knobs import is_async_cow_enabled

                if is_async_cow_enabled() and not self.force_clone:
                    # COW: checksums already recorded from the live
                    # bytes — skip the clone and verify at write time.
                    self.cow_pending = True
                    return mv
                from .. import _native
                from ..knobs import get_native_copy_threads

                out = _acquire_clone_buffer(mv.nbytes)
                # checksums already recorded
                _native.memcpy(out, mv, nthreads=get_native_copy_threads())
                return out
            return staged
        if (
            relaid is None
            and self.is_async_snapshot
            and self._aliases_caller_memory
        ):
            # Defensive clone: training resumes before I/O completes, and a
            # donated buffer could be overwritten under us. The native
            # memcpy releases the GIL (and parallelizes) for large clones
            # — and when checksums are on, the CRC is computed INSIDE the
            # clone pass (one read per byte instead of two), since the
            # clone is the async take's blocked time. In deferred mode
            # the clone is a plain memcpy and hashing happens on the
            # write path (late_checksum).
            from ..knobs import is_async_cow_enabled

            if want_crc and is_async_cow_enabled() and not self.force_clone:
                # COW (the default): no clone at all — record the fused hash
                # of the LIVE bytes now (overriding deferral: the
                # stage-time value is the mutation-detection reference)
                # and have the write pipeline re-verify after the
                # storage write. Frozen layers pay one read pass and
                # zero allocation inside the blocked window.
                _record_checksums(self.entry, mv, self.record_dedup_hashes)
                self.cow_pending = True
                return mv
            from .. import _native
            from ..knobs import get_native_copy_threads

            # Internal fan-out of each native pass is divided by the
            # executor thread count so the TOTAL copy-thread budget
            # stays constant (the ROADMAP 5 anomaly was this nesting).
            copy_threads = get_native_copy_threads()
            out = _acquire_clone_buffer(mv.nbytes)
            if want_crc and self.defer_checksums:
                _native.memcpy(out, mv, nthreads=copy_threads)
                return out
            if want_crc:
                tile_rows, row_nbytes = _tile_geometry(self.entry, mv.nbytes)
                want_dedup = _want_dedup_hashes(
                    self.record_dedup_hashes, tile_rows, mv.nbytes
                )
                if tile_rows:
                    if want_dedup:
                        crcs, xxhs = _native.memcpy_crc_xxh_tiles(
                            out, mv, tile_rows * row_nbytes,
                            nthreads=copy_threads,
                        )
                    else:
                        crcs = _native.memcpy_crc_tiles(
                            out, mv, tile_rows * row_nbytes,
                            nthreads=copy_threads,
                        )
                        xxhs = None
                    _annotate_checksums(
                        self.entry, crcs, tile_rows, row_nbytes, tile_xxhs=xxhs
                    )
                elif want_dedup:
                    # Tile-less blob needing the 64-bit dedup hash: XXH64
                    # has no combine, so the fused clone+hash runs as one
                    # tile (single-threaded copy; tile-less dedup-hashed
                    # blobs are small or rare (1, huge) shapes).
                    crcs, xxhs = _native.memcpy_crc_xxh_tiles(
                        out, mv, mv.nbytes
                    )
                    _annotate_checksums(
                        self.entry, crcs, 0, row_nbytes, whole_xxh=xxhs[0]
                    )
                else:
                    # Whole-blob checksum: still clone in internal
                    # sub-tiles so the copy parallelizes (a (1, huge)
                    # array maps to ONE checksum tile — without this the
                    # fused pass would run single-threaded), then fold
                    # the sub-tile values into the one recorded CRC.
                    sub = 16 << 20
                    crcs = _native.memcpy_crc_tiles(
                        out, mv, sub, nthreads=copy_threads
                    )
                    combined = _fold_crcs(
                        crcs, _tile_lengths(mv.nbytes, sub, len(crcs))
                    )
                    _annotate_checksums(
                        self.entry, [combined], 0, row_nbytes
                    )
            else:
                _native.memcpy(out, mv, nthreads=copy_threads)
            return out
        if want_crc and not self.defer_checksums:
            _record_checksums(self.entry, mv, self.record_dedup_hashes)
        return staged

    def late_checksum(self, buf) -> None:
        """Record checksums from the STAGED buffer — called by the write
        pipeline when ``defer_checksums`` is set (the buffer is stable:
        either the caller's own memory on a sync take or the defensive
        clone on an async one)."""
        from ..knobs import is_checksum_disabled

        if (
            self.entry is None
            or is_checksum_disabled()
            or self.entry.checksum is not None
        ):
            return
        _record_checksums(
            self.entry,
            memoryview(buf).cast("B"),
            self.record_dedup_hashes,
        )

    def verify_cow_after_write(self, buf) -> None:
        """COW staging: re-hash the live bytes AFTER the storage write
        and compare against the checksum recorded inside the blocked
        window. A mismatch means the caller mutated this array while
        the async take was in flight — the written blob may hold torn
        data, so the take fails here (the metadata is never committed)
        instead of silently snapshotting a state that never existed."""
        if self.entry is None or self.entry.checksum is None:
            return
        from .. import _native

        try:
            mv = memoryview(buf).cast("B")
            _native.verify_checksum(mv, self.entry.checksum, self.entry.location)
            self._verify_cow_xxh_lane(mv)
        except Exception as e:
            raise RuntimeError(
                f"async COW take detected a concurrent mutation of "
                f"{self.entry.location!r}: the array changed between "
                "staging and its storage write. Under TPUSNAP_ASYNC_COW "
                "the live bytes stay aliased until each blob's write "
                "completes — mutate state only after "
                "PendingSnapshot.wait_staged()/wait() returns (both are "
                "COW-aware and block until the writes drain), or unset "
                "TPUSNAP_ASYNC_COW to restore defensive cloning."
            ) from e

    def _verify_cow_xxh_lane(self, mv) -> None:
        """Re-verify the 64-bit XXH64 dedup lane too, when recorded
        (incremental takes, small eagerly-hashed blobs) — the CRC32C
        lane alone is 32 bits of mutation evidence; with the dedup lane
        the pair matches what dedup skips require. Lanes recorded by a
        different build's algorithm are skipped, mirroring
        verify_checksum's policy."""
        entry = self.entry
        from .. import _native

        dalgo = _native.dedup_hash_algorithm()
        if entry.dedup_hash is not None:
            algo, _, val = entry.dedup_hash.partition(":")
            if algo == dalgo and int(val, 16) != _native.xxh64(mv):
                raise _native.ChecksumError(
                    f"XXH64 lane mismatch for {entry.location!r}"
                )
            return
        if not entry.tile_dedup_hashes:
            return
        tile_rows, row_nbytes = _tile_geometry(entry, mv.nbytes)
        if not tile_rows:
            return
        tile_nbytes = tile_rows * row_nbytes
        for i, recorded in enumerate(entry.tile_dedup_hashes):
            algo, _, val = recorded.partition(":")
            if algo != dalgo:
                return
            tile = mv[i * tile_nbytes : (i + 1) * tile_nbytes]
            if int(val, 16) != _native.xxh64(tile):
                raise _native.ChecksumError(
                    f"XXH64 tile {i} mismatch for {entry.location!r}"
                )

    def _stage_compressed(self, mv: memoryview) -> BufferType:
        """Fused shuffle+LZ4+dual-hash staging pass: one read of the
        live bytes, compressed tiles + their checksums/dedup hashes out
        (all recorded over the STORED bytes — the journal/salvage/
        upload-journal evidence rule holds unchanged). The staged
        buffer is copied to a right-sized pool buffer so resident bytes
        match what the scheduler's budget credits back."""
        from .. import _native, telemetry
        from ..compress import codec_elem
        from ..knobs import get_native_copy_threads

        codec = self.compress_codec
        entry = self.entry
        tile_rows, row_nbytes = _tile_geometry(entry, mv.nbytes)
        tile_nbytes = tile_rows * row_nbytes if tile_rows else mv.nbytes
        want_dedup = _want_dedup_hashes(
            self.record_dedup_hashes, tile_rows, mv.nbytes
        ) or self.dedup_entry is not None
        rec = telemetry.current()
        # Raw-hash fast skip: an unchanged blob must cost a multi-GB/s
        # hash pass, not a codec pass (a mostly-frozen model streaming
        # micro-commits over a slow pipe would otherwise re-compress
        # the whole model per cadence interval to write ~zero bytes).
        # The codec is deterministic, so equal RAW bytes imply equal
        # stored bytes — the base's recorded dual raw hash (96 bits,
        # stronger than the 64-bit skip-evidence floor) licenses
        # adopting its stored blob and every recorded field wholesale.
        raw_hash = _raw_dual_hash(mv) if want_dedup else None
        prev = self.dedup_entry
        if (
            prev is not None
            and raw_hash is not None
            and getattr(prev, "uncompressed_dedup_hash", None) == raw_hash
            and getattr(prev, "codec", None) == codec
            and prev.checksum is not None
            and prev.dtype == entry.dtype
            and list(prev.shape) == list(entry.shape)
            and prev.serializer == entry.serializer
        ):
            from ..io_types import SKIP_WRITE

            entry.location = prev.location
            entry.byte_range = (
                list(prev.byte_range)
                if prev.byte_range is not None
                else None
            )
            _annotate_from_dedup_base(entry, prev)
            telemetry.incr("compress.raw_dedup_skips", rec=rec)
            return SKIP_WRITE
        t0 = rec.now() if rec is not None else 0.0
        out, comp_sizes, crcs, xxhs = _native.compress_tiles(
            mv,
            tile_nbytes,
            codec_elem(codec),
            want_dedup,
            nthreads=get_native_copy_threads(),
        )
        if rec is not None:
            rec.record_span(
                "compress",
                t0,
                rec.now() - t0,
                kind=telemetry.WORK,
                bytes=mv.nbytes,
                out_bytes=out.nbytes,
                codec=codec,
            )
        telemetry.incr("compress.bytes_in", mv.nbytes, rec=rec)
        telemetry.incr("compress.bytes_out", out.nbytes, rec=rec)
        _annotate_compressed(
            entry, codec, mv.nbytes, comp_sizes, crcs, tile_rows, xxhs
        )
        if raw_hash is not None:
            # Write-skip evidence for the NEXT incremental take's
            # raw-hash fast path (see above) — never storage evidence.
            entry.uncompressed_dedup_hash = raw_hash
        if self.dedup_entry is not None and dedup_entries_match(
            entry, self.dedup_entry
        ):
            # Deterministic codec: unchanged input bytes yield identical
            # compressed bytes, so the compressed-hash comparison is as
            # strong as the uncompressed one (a base written by a
            # different codec/build conservatively rewrites — codec is
            # part of the match identity).
            from ..io_types import SKIP_WRITE

            entry.location = self.dedup_entry.location
            entry.byte_range = (
                list(self.dedup_entry.byte_range)
                if self.dedup_entry.byte_range is not None
                else None
            )
            return SKIP_WRITE
        # `out` slices a worst-case-bound allocation; re-home the
        # compressed bytes in a right-sized (aligned, O_DIRECT-ready)
        # pool buffer so the big bound buffer is not pinned until the
        # storage write drains.
        final = _acquire_clone_buffer(out.nbytes)
        _native.memcpy(final, out, nthreads=get_native_copy_threads())
        return final

    def get_staging_cost_bytes(self) -> int:
        n = self.get_planned_bytes()
        if self.compress_codec is not None:
            # Compressed staging transiently holds the worst-case-bound
            # output buffer plus the right-sized staged copy; 2x the
            # payload bounds both (and matches the async-clone model).
            return 2 * n
        if self.is_async_snapshot:
            from ..knobs import is_async_cow_enabled, is_checksum_disabled

            if (
                is_async_cow_enabled()
                and not self.force_clone
                and self.entry is not None
                and not is_checksum_disabled()
            ):
                # COW staging (same conditions as _stage_blocking's COW
                # branches): no second host copy is ever held — the
                # live bytes are written directly and verified by hash.
                return n
            # Defensive clone: a second host copy while in flight.
            return 2 * n
        return n

    def get_planned_bytes(self) -> int:
        """Payload bytes (the progress denominator) — never doubled by
        the async clone's staging-cost accounting."""
        if self.array_prepare_func is not None and self.entry is not None:
            # What will actually be staged is the transformed array.
            return tensor_nbytes(self.entry.dtype, self.entry.shape)
        return array_nbytes(self.arr)


# platform name -> does np.asarray of a device array ALIAS the XLA
# buffer (vs materializing a fresh host copy)? Probed empirically once
# per backend: a hardcoded platform assumption here would decide
# whether every async take pays a full clone pass.
_ASARRAY_ALIASES_BY_PLATFORM: dict = {}


def _asarray_aliases_device_buffer(device) -> bool:
    """Probe whether ``np.asarray`` of an array on ``device`` returns a
    VIEW of the XLA buffer (CPU backends: zero-copy, so donation could
    overwrite it) or a fresh host copy (real TPU/GPU: DtoH materializes
    new host memory donation never touches). Compares the host array's
    data pointer against the device buffer's. A runtime that cannot
    answer the probe gets the safe result, "may alias" (async takes
    clone), logged once — never a guess from the platform's name."""
    platform = getattr(device, "platform", "unknown")
    cached = _ASARRAY_ALIASES_BY_PLATFORM.get(platform)
    if cached is not None:
        return cached
    try:
        probe = jax.device_put(np.arange(32, dtype=np.uint8), device)
        host = np.asarray(probe)
        aliases = bool(
            host.__array_interface__["data"][0]
            == probe.unsafe_buffer_pointer()
        )
    except Exception as e:
        logger.warning(
            "cannot probe whether np.asarray aliases %s device buffers "
            "(%s: %s); async takes will clone",
            platform,
            type(e).__name__,
            e,
        )
        aliases = True
    _ASARRAY_ALIASES_BY_PLATFORM[platform] = aliases
    return aliases


def _may_alias_live_memory(arr: ArrayLike, host: np.ndarray) -> bool:
    """Whether the staged host buffer could alias memory the training
    loop may overwrite (donation) — if so, an async snapshot must clone
    it before returning control.

    On NON-CPU backends (TPU/GPU) the answer is no: ``np.asarray`` of a
    device array materializes a fresh host copy via DtoH — donation
    reuses device HBM, never that host buffer — so async takes on real
    accelerators skip the defensive clone entirely and their blocked
    time is DMA alone (non-incremental takes at any world size defer
    the hash to the write path; multi-process manifests receive the
    late values via the commit barrier's KV store — see
    snapshot._LateChecksums). Rather than
    trusting the platform name, the aliasing behavior is PROBED once
    per backend (``_asarray_aliases_device_buffer``). Host-resident
    (pinned_host, the UVM analog) arrays alias host memory on any
    backend, and numpy sources alias the caller's array by
    construction — those always clone."""
    if isinstance(arr, jax.Array):
        if is_host_resident(arr):
            return True
        try:
            return any(
                _asarray_aliases_device_buffer(d) for d in arr.devices()
            )
        except Exception:
            return True
    return True


class DonatedBeforeStagedError(RuntimeError):
    """A take's source array was deleted before it was staged."""


def _relayout(host: np.ndarray) -> Optional[np.ndarray]:
    """``host``'s bytes in C order in a buffer of the staging pool (warm
    pages from the second take on; the write pipeline returns it after
    the write), copied in pieces on the native copy threads' budget; or
    None for an array staging leaves as it is: C-contiguous, or under
    ``RELAYOUT_MIN_BYTES``. Read off the array alone."""
    if host.flags.c_contiguous or host.nbytes < RELAYOUT_MIN_BYTES:
        return None
    from ..knobs import get_native_copy_threads

    with telemetry.span("relayout", bytes=host.nbytes):
        out = _acquire_clone_buffer(host.nbytes)
        c_order_copy_into(out, host, get_native_copy_threads())
    telemetry.incr("stage.relayouts")
    telemetry.incr("stage.relayout_bytes", host.nbytes)
    return out


def _release_clone_buffer(buf: Optional[np.ndarray]) -> None:
    """Hand a pool buffer back where staging ended with other bytes
    than its own (a compressed blob, a dedup skip); None is ignored."""
    from .._staging_pool import release

    release(buf)


def _acquire_clone_buffer(nbytes: int):
    """Aligned buffer for the async defensive clone, from the staging
    pool: steady-state checkpoint loops reuse warm pages instead of
    paying ~1 GB/s first-touch page zeroing per take (the dominant cost
    of the blocked window on CPU-backend hosts). The write pipeline
    returns it to the pool after the write."""
    from .._staging_pool import acquire

    return acquire(nbytes)


def writable_byte_view(
    arr: Optional[ArrayLike], dtype: str, shape: Sequence[int]
) -> Optional[memoryview]:
    """Flat writable byte view over ``arr`` when the stored blob's bytes
    may land there verbatim: numpy, writable, C-contiguous, exact
    dtype/shape match. Used as the destination of in-place reads — the
    storage plugin DMAs straight into the restore target and the
    deserialize+copy pass disappears."""
    if not isinstance(arr, np.ndarray):
        return None
    if not (arr.flags.writeable and arr.flags.c_contiguous):
        return None
    try:
        if dtype_to_string(arr.dtype) != dtype or list(arr.shape) != list(shape):
            return None
    except ValueError:
        return None
    try:
        mv = array_as_memoryview(arr)
    except ValueError:
        return None
    # array_as_memoryview copies non-contiguous inputs; contiguity was
    # checked above, so this view aliases arr's memory.
    return mv if not mv.readonly else None


def _want_crc(entry: TensorEntry) -> bool:
    from ..knobs import is_checksum_disabled

    return entry.checksum is not None and not is_checksum_disabled()


def dedup_entries_match(new: TensorEntry, prev: TensorEntry) -> bool:
    """True when the freshly staged blob (``new``, checksums recorded) is
    byte-identical to the previous snapshot's blob per its recorded
    checksums — same dtype/shape/serializer, same whole-blob CRC, and the
    same tile-grain CRCs (a changed tile-size knob between takes makes
    geometries differ and conservatively fails the match).

    Equality needs MORE than one 32-bit CRC of evidence per unit of
    skipped data (ADVICE r3/r4: a changed blob whose CRC collides with
    the base's silently restores stale data, a ~2^-32 channel per
    blob-take at fleet scale — and a change confined to ONE tile rests
    on that tile's single CRC, however many unchanged tiles also
    match): tiled blobs must carry matching 64-bit per-tile
    ``tile_dedup_hashes`` on BOTH sides, and tile-less blobs a matching
    64-bit ``dedup_hash`` on BOTH sides — a base without the hashes
    (older format, non-incremental take, or a blob above the eager-hash
    size) conservatively rewrites."""
    if not (
        prev.checksum is not None
        and new.checksum == prev.checksum
        and new.dtype == prev.dtype
        and list(new.shape) == list(prev.shape)
        and new.serializer == prev.serializer
        and new.tile_rows == prev.tile_rows
        and new.tile_checksums == prev.tile_checksums
        # Compressed blobs: hashes are over STORED bytes, so identity
        # includes the codec and the stored layout — a codec change
        # between takes (or compressed vs raw) conservatively rewrites.
        and getattr(new, "codec", None) == getattr(prev, "codec", None)
        and getattr(new, "comp_tile_sizes", None)
        == getattr(prev, "comp_tile_sizes", None)
    ):
        return False
    if new.tile_checksums:
        return bool(
            new.tile_dedup_hashes
            and prev.tile_dedup_hashes
            and new.tile_dedup_hashes == prev.tile_dedup_hashes
        )
    return (
        new.dedup_hash is not None
        and prev.dedup_hash is not None
        and new.dedup_hash == prev.dedup_hash
    )


def _tile_lengths(nbytes: int, tile_nbytes: int, n_tiles: int) -> List[int]:
    """Byte length of each of ``n_tiles`` consecutive tiles of
    ``tile_nbytes`` covering ``nbytes`` (last tile short)."""
    return [
        min((i + 1) * tile_nbytes, nbytes) - i * tile_nbytes
        for i in range(n_tiles)
    ]


def _fold_crcs(crcs: List[int], lengths: List[int]) -> int:
    """Combine per-tile seed-0 CRC values (with their byte lengths) into
    the CRC of the concatenation — the ONE fold used by every writer and
    verifier, so their boundary math cannot drift apart."""
    from .. import _native

    combined = crcs[0] & 0xFFFFFFFF
    for c, ln in zip(crcs[1:], lengths[1:]):
        combined = _native.crc_combine(combined, c & 0xFFFFFFFF, ln)
    return combined & 0xFFFFFFFF


def _tile_geometry(entry: TensorEntry, nbytes: int) -> Tuple[int, int]:
    """(tile_rows, row_nbytes) for tile-grain checksums of this entry's
    bytes, with tile_rows == 0 when the blob gets one whole-blob value.
    Shared by the sync hash pass and the async fused clone+hash pass so
    both record byte-identical manifests."""
    from ..knobs import get_tile_checksum_bytes

    shape = entry.shape
    n_rows = shape[0] if shape else 0
    row_nbytes = nbytes // n_rows if n_rows else 0
    tile_rows = (
        max(1, get_tile_checksum_bytes() // row_nbytes) if row_nbytes else 0
    )
    if n_rows > tile_rows >= 1:
        return tile_rows, row_nbytes
    return 0, row_nbytes


# Tile-less blobs at or below this size record their 64-bit dedup hash
# on EVERY take (cheap; lets the first increment against any base dedup
# them). Larger tile-less blobs — rare (1, huge)-shaped arrays whose
# hash pass is a real cost — record it only on incremental takes.
_DEDUP_HASH_EAGER_MAX = 64 << 20


def _want_dedup_hashes(record_flag: bool, tile_rows: int, nbytes: int) -> bool:
    if tile_rows:
        return record_flag
    return record_flag or nbytes <= _DEDUP_HASH_EAGER_MAX


def _annotate_checksums(
    entry: TensorEntry,
    tile_crcs: List[int],
    tile_rows: int,
    row_nbytes: int,
    tile_xxhs: Optional[List[int]] = None,
    whole_xxh: Optional[int] = None,
) -> None:
    """Record per-tile + combined whole-blob checksums into ``entry``
    from raw seed-0 CRC values (one per tile, or a single whole-blob
    value when ``tile_rows`` is 0), plus the optional 64-bit dedup
    hashes (per tile, or whole-blob)."""
    from .. import _native

    algo = _native.checksum_algorithm()
    if tile_rows:
        n_rows = entry.shape[0]
        combined = _fold_crcs(
            tile_crcs,
            _tile_lengths(
                n_rows * row_nbytes, tile_rows * row_nbytes, len(tile_crcs)
            ),
        )
        entry.tile_rows = tile_rows
        entry.tile_checksums = [
            f"{algo}:{crc & 0xFFFFFFFF:08x}" for crc in tile_crcs
        ]
        entry.checksum = f"{algo}:{combined:08x}"
        if tile_xxhs is not None:
            dalgo = _native.dedup_hash_algorithm()
            entry.tile_dedup_hashes = [
                f"{dalgo}:{x & _XXH_MASK:016x}" for x in tile_xxhs
            ]
    else:
        entry.checksum = f"{algo}:{tile_crcs[0] & 0xFFFFFFFF:08x}"
        if whole_xxh is not None:
            dalgo = _native.dedup_hash_algorithm()
            entry.dedup_hash = f"{dalgo}:{whole_xxh & _XXH_MASK:016x}"


_XXH_MASK = (1 << 64) - 1


def _annotate_compressed(
    entry: TensorEntry,
    codec: str,
    raw_nbytes: int,
    comp_sizes: List[int],
    tile_crcs: List[int],
    tile_rows: int,
    tile_xxhs: Optional[List[int]] = None,
) -> None:
    """Record the compressed-blob manifest fields: codec identity,
    logical size, per-tile stored sizes, and checksums/dedup hashes
    computed over the STORED (compressed) bytes — the whole-blob value
    is the CRC combine over the compressed tile lengths, so scrub, the
    journal's written-bytes evidence and restore verification all agree
    byte-for-byte with what is on disk."""
    from .. import _native

    algo = _native.checksum_algorithm()
    entry.codec = codec
    entry.uncompressed_nbytes = raw_nbytes
    entry.comp_tile_sizes = [int(s) for s in comp_sizes]
    if tile_rows:
        entry.tile_rows = tile_rows
        entry.tile_checksums = [
            f"{algo}:{crc & 0xFFFFFFFF:08x}" for crc in tile_crcs
        ]
        entry.checksum = (
            f"{algo}:{_fold_crcs(tile_crcs, entry.comp_tile_sizes):08x}"
        )
        if tile_xxhs is not None:
            dalgo = _native.dedup_hash_algorithm()
            entry.tile_dedup_hashes = [
                f"{dalgo}:{x & _XXH_MASK:016x}" for x in tile_xxhs
            ]
    else:
        entry.checksum = f"{algo}:{tile_crcs[0] & 0xFFFFFFFF:08x}"
        if tile_xxhs is not None:
            dalgo = _native.dedup_hash_algorithm()
            entry.dedup_hash = f"{dalgo}:{tile_xxhs[0] & _XXH_MASK:016x}"


def _raw_dual_hash(mv: memoryview) -> str:
    """Dual hash of a compressed stager's RAW payload bytes —
    ``uncompressed_dedup_hash`` write-skip evidence. One fused-speed
    read per algorithm; only computed on dedup-recording takes."""
    from .. import _native

    algo = _native.checksum_algorithm()
    crc = _native.crc32c(mv) & 0xFFFFFFFF
    xxh = _native.xxh64(mv) & _XXH_MASK
    return f"{algo}:{crc:08x}+xxh64:{xxh:016x}"


def _annotate_from_dedup_base(entry: TensorEntry, prev: TensorEntry) -> None:
    """A raw-hash fast skip never ran the codec, so the entry adopts
    the base's recorded representation wholesale — codec identity,
    stored layout and every stored-bytes integrity field. The codec is
    deterministic, so these are byte-identical to what re-compressing
    would have produced."""
    entry.codec = prev.codec
    entry.uncompressed_nbytes = prev.uncompressed_nbytes
    entry.comp_tile_sizes = (
        list(prev.comp_tile_sizes)
        if prev.comp_tile_sizes is not None
        else None
    )
    entry.tile_rows = prev.tile_rows
    entry.checksum = prev.checksum
    entry.tile_checksums = (
        list(prev.tile_checksums)
        if prev.tile_checksums is not None
        else None
    )
    entry.dedup_hash = prev.dedup_hash
    entry.tile_dedup_hashes = (
        list(prev.tile_dedup_hashes)
        if prev.tile_dedup_hashes is not None
        else None
    )
    entry.uncompressed_dedup_hash = prev.uncompressed_dedup_hash


def _record_checksums(
    entry: TensorEntry, mv: memoryview, record_dedup_hashes: bool = False
) -> None:
    """Record integrity checksums into ``entry`` at stage time.

    Blobs large enough to be read under a memory budget are hashed in
    row-tiles (``tile_rows``/``tile_checksums``) and the whole-blob value
    derived by CRC combine — one hash pass either way. Budget-tiled
    reads align to these boundaries and verify by combining the covered
    tiles' values (beyond the reference, which has no end-to-end
    integrity checking at all).

    ``record_dedup_hashes`` (incremental takes) additionally records the
    64-bit XXH64 dedup hashes — per tile, fused into the same memory
    pass — so the next increment's dedup decisions carry more than 32
    bits of evidence per skipped unit. Small tile-less blobs record
    theirs on every take (see _DEDUP_HASH_EAGER_MAX)."""
    with telemetry.span("checksum", bytes=mv.nbytes):
        _record_checksums_impl(entry, mv, record_dedup_hashes)


def _record_checksums_impl(
    entry: TensorEntry, mv: memoryview, record_dedup_hashes: bool
) -> None:
    from .. import _native
    from ..knobs import get_native_copy_threads

    tile_rows, row_nbytes = _tile_geometry(entry, mv.nbytes)
    want_dedup = _want_dedup_hashes(record_dedup_hashes, tile_rows, mv.nbytes)
    if tile_rows:
        n_rows = entry.shape[0]
        if want_dedup:
            # Tile boundaries are uniform except the last; the fused
            # native pass tiles by byte count, which matches exactly.
            # Internal fan-out divided by the stage-thread count — the
            # dedup hash pass is the hot pass of every delta-stream
            # micro-commit and must honor the same total-copy-thread
            # budget as the clone passes.
            crcs, xxhs = _native.crc_xxh_tiles(
                mv, tile_rows * row_nbytes,
                nthreads=get_native_copy_threads(),
            )
            _annotate_checksums(
                entry, crcs, tile_rows, row_nbytes, tile_xxhs=xxhs
            )
            return
        crcs = [
            _native.crc32c(
                mv[r0 * row_nbytes : min(r0 + tile_rows, n_rows) * row_nbytes]
            )
            for r0 in range(0, n_rows, tile_rows)
        ]
        _annotate_checksums(entry, crcs, tile_rows, row_nbytes)
        return
    if want_dedup:
        crcs, xxhs = _native.crc_xxh_tiles(
            mv, mv.nbytes, nthreads=get_native_copy_threads()
        )
        _annotate_checksums(entry, crcs, 0, row_nbytes, whole_xxh=xxhs[0])
        return
    _annotate_checksums(entry, [_native.crc32c(mv)], 0, row_nbytes)


def combined_tile_checksum(
    entry: TensorEntry, r0: int, r1: int, row_nbytes: int
) -> Optional[str]:
    """Expected checksum of rows [r0, r1) derived from recorded tile
    checksums, or None when the range is not verifiable (no tiles
    recorded, boundaries misaligned, or the snapshot was written by a
    build with a different checksum algorithm — combining with the wrong
    polynomial would manufacture false corruption reports)."""
    from .. import _native

    t = entry.tile_rows
    if not entry.tile_checksums or not t:
        return None
    n_rows = entry.shape[0]
    if r0 % t != 0 or (r1 != n_rows and r1 % t != 0):
        return None
    algo = _native.checksum_algorithm()
    crcs: List[int] = []
    lengths: List[int] = []
    for i in range(r0 // t, math.ceil(r1 / t)):
        tile = entry.tile_checksums[i]
        tile_algo, _, value = tile.partition(":")
        if tile_algo != algo:
            return None
        try:
            crcs.append(int(value, 16))
        except ValueError:
            return None
        tr1 = min((i + 1) * t, n_rows)
        lengths.append((tr1 - i * t) * row_nbytes)
    if not crcs:
        return None
    return f"{algo}:{_fold_crcs(crcs, lengths):08x}"


class ArrayBufferConsumer(BufferConsumer):
    """Deserializes into the restore target. For jax targets the result is
    device_put with the target's sharding; numpy targets are filled in
    place (the reference's in-place load, tensor.py:188-196) — and when
    the storage plugin supports it, the read lands in the target's own
    memory with the checksum computed inside the read (``consume_read_io``
    then verifies a 4-byte value and the consume stage does no data pass
    at all)."""

    def __init__(
        self,
        entry: TensorEntry,
        obj_out: Optional[ArrayLike],
        fut: Future,
        verify_location: str = "",
    ):
        self.entry = entry
        self.obj_out = obj_out
        self.fut = fut
        self.verify_location = verify_location or entry.location
        self.into_mv = writable_byte_view(obj_out, entry.dtype, entry.shape)

    async def consume_read_io(self, read_io, executor: Optional[Executor] = None) -> None:
        hashed = self._verify_read_time_checksum(read_io)
        if read_io.in_place:
            # Bytes are already in obj_out's memory.
            self.fut.obj = self.obj_out
            return
        await self.consume_buffer(read_io.buf.getbuffer(), executor, verify=not hashed)

    def _verify_read_time_checksum(self, read_io) -> bool:
        """Where the storage plug-in hashed the bytes as it read them (in
        place, or into its scratch buffer on the reader thread, beside the
        other streams' I/O), verify that value against the manifest: an
        int compare, no data pass. False: it did not, and a buffer still
        has to be hashed."""
        if self.entry.checksum is None or read_io.crc32c is None:
            return False
        from .. import _native

        _native.verify_checksum_value(
            read_io.crc32c,
            read_io.crc_algo,
            self.entry.checksum,
            self.verify_location,
        )
        return True

    async def consume_buffer(
        self, buf: BufferType, executor: Optional[Executor] = None, verify: bool = True
    ) -> None:
        if executor is not None:
            await _consume_handoff(executor, self._consume_blocking, buf, verify)
        else:
            self._consume_blocking(buf, verify)

    def _consume_blocking(self, buf: BufferType, verify: bool = True) -> None:
        if verify:
            with telemetry.span("decode", bytes=memoryview(buf).nbytes):
                _maybe_verify(buf, self.entry.checksum, self.verify_location)
        value = materialize_array(self.entry, buf, self.obj_out)
        self.fut.obj = value

    def get_consuming_cost_bytes(self) -> int:
        return tensor_nbytes(self.entry.dtype, self.entry.shape)


async def _consume_handoff(executor: Executor, fn: Callable, *args: Any) -> Any:
    """A consumer's blocking body on the consume executor: the wait for
    a consume thread is ``consume.queued``; the body records its own
    ``decode`` (checksum verify, decompress, copy into a host target) and
    ``htod`` (``device_put`` into a jax target) spans; how late the loop
    was afterwards is ``consume.resumed``."""
    return await telemetry.run_handoff(executor, "consume", fn, *args, work=False)


def _maybe_verify(buf: BufferType, checksum: Optional[str], location: str) -> None:
    """Verify a read buffer against a manifest checksum (knob-gated).
    Callers reading a sub-range of an entry's bytes pass the combined
    tile checksum for that range (``combined_tile_checksum``), or None
    when the range is not verifiable."""
    if checksum is None:
        return
    from ..knobs import is_checksum_disabled

    if is_checksum_disabled():
        return
    from .. import _native

    _native.verify_checksum(memoryview(buf).cast("B"), checksum, location)


def _owning_copy(src: np.ndarray) -> np.ndarray:
    """A copy of ``src`` that owns its memory, faulted as hugepages when
    large (np.copy would first-touch a multi-GB destination 4 KiB at a
    time, which on few-core hosts rivals the I/O cost)."""
    from .. import _native

    out = _native.empty_advised(src.shape, src.dtype)
    np.copyto(out, src)
    return out


def finalize_into_target(
    host: np.ndarray, obj_out: Optional[ArrayLike], owns_memory: bool
) -> ArrayLike:
    """Land a deserialized host array in the restore target — the ONE
    implementation of the cast-into-target semantics (the reference's
    tensor_copy, io_preparers/tensor.py:383-403) shared by the
    whole-blob and tiled/chunked paths:

    - same-shape writable numpy target: filled IN PLACE, cast to the
      target's dtype when it differs (a bf16-saved blob restores into an
      f32 training target upcast);
    - jax target: ``device_put`` with the target's sharding at the
      STORED dtype (half the HtoD bytes for reduced-precision saves),
      then cast ON DEVICE to the target's dtype when it differs;
    - otherwise: a host array owning its memory (``owns_memory`` says
      whether ``host`` already does, or aliases a transient read
      buffer)."""
    if isinstance(obj_out, jax.Array):
        # device_put is async; XLA overlaps the HtoD DMA with further
        # reads. The dtype cast (if any) runs on the accelerator with
        # the sharding preserved — not as a host pass that would double
        # the transfer volume. `htod` is the call and whatever it
        # dispatches, not the DMA's own time on the bus.
        with telemetry.span("htod", bytes=host.nbytes):
            dev = jax.device_put(host, obj_out.sharding)
            if obj_out.dtype != dev.dtype and obj_out.shape == dev.shape:
                dev = dev.astype(obj_out.dtype)
        return dev
    with telemetry.span("decode", bytes=host.nbytes):
        if (
            isinstance(obj_out, np.ndarray)
            and obj_out.shape == host.shape
            and obj_out.flags.writeable
        ):
            np.copyto(obj_out, host, casting="unsafe")
            return obj_out
        return host if owns_memory else _owning_copy(host)


def materialize_array(
    entry: TensorEntry, buf: BufferType, obj_out: Optional[ArrayLike]
) -> ArrayLike:
    src = array_from_memoryview(memoryview(buf), entry.dtype, entry.shape)
    # `src` aliases the read buffer (about to be released) — any bare
    # return must copy.
    return finalize_into_target(src, obj_out, owns_memory=False)


def trace_array_prepare(
    arr: ArrayLike,
    array_prepare_func: Optional[Callable[[ArrayLike, bool], ArrayLike]],
) -> Tuple[str, List[int]]:
    """The (dtype, shape) the manifest must record for ``arr`` under an
    optional save-time transform — discovered WITHOUT computing the
    transform when possible: jax transforms are traced via
    ``jax.eval_shape`` (abstract evaluation, zero FLOPs — the TPU-first
    analog of the reference's tracing=True call on a real tensor,
    io_preparers/tensor.py:57-66); non-traceable transforms fall back to
    one real call whose result is discarded. Shape changes are rejected
    like the reference's."""
    if array_prepare_func is None:
        return dtype_to_string(arr.dtype), list(arr.shape)
    import functools

    try:
        traced = jax.eval_shape(
            functools.partial(array_prepare_func, tracing=True), arr
        )
    except Exception:
        traced = array_prepare_func(arr, True)  # tracing=True
    if list(traced.shape) != list(arr.shape):
        raise RuntimeError(
            "_custom_array_prepare_func must not change the array's "
            f"shape (changed from {list(arr.shape)} to {list(traced.shape)})"
        )
    return dtype_to_string(traced.dtype), list(traced.shape)


class ArrayIOPreparer:
    """prepare_write/prepare_read for dense (single-blob) arrays
    (reference TensorIOPreparer, io_preparers/tensor.py:47-222)."""

    @staticmethod
    def prepare_write(
        storage_path: str,
        arr: ArrayLike,
        replicated: bool = False,
        is_async_snapshot: bool = False,
        array_prepare_func: Optional[Callable[[ArrayLike, bool], ArrayLike]] = None,
        array_prepare_traced: Optional[Tuple[str, List[int]]] = None,
        prev_entry: Optional[object] = None,
        record_dedup_hashes: bool = False,
    ) -> Tuple[TensorEntry, List[WriteReq]]:
        if array_prepare_traced is not None:
            dtype, shape = array_prepare_traced[0], list(array_prepare_traced[1])
        else:
            dtype, shape = trace_array_prepare(arr, array_prepare_func)
        entry = TensorEntry(
            location=storage_path,
            serializer=Serializer.BUFFER_PROTOCOL.value,
            dtype=dtype,
            shape=shape,
            replicated=replicated,
        )
        write_reqs = [
            WriteReq(
                path=storage_path,
                buffer_stager=ArrayBufferStager(
                    arr,
                    is_async_snapshot,
                    entry=entry,
                    array_prepare_func=array_prepare_func,
                    dedup_entry=(
                        prev_entry
                        if isinstance(prev_entry, TensorEntry)
                        else None
                    ),
                    record_dedup_hashes=record_dedup_hashes,
                ),
            )
        ]
        return entry, write_reqs

    @staticmethod
    def prepare_read(
        entry: TensorEntry,
        obj_out: Optional[ArrayLike] = None,
        buffer_size_limit_bytes: Optional[int] = None,
        logical_path: str = "",
    ) -> Tuple[List[ReadReq], Future]:
        fut: Future = Future()
        if entry.codec:
            return ArrayIOPreparer._prepare_compressed_read(
                entry, obj_out, buffer_size_limit_bytes, fut, logical_path
            )
        nbytes = tensor_nbytes(entry.dtype, entry.shape)
        if (
            buffer_size_limit_bytes is not None
            and nbytes > buffer_size_limit_bytes
            and len(entry.shape) > 0
            and entry.shape[0] > 1
        ):
            return ArrayIOPreparer._prepare_tiled_read(
                entry, obj_out, buffer_size_limit_bytes, fut, logical_path
            )
        byte_range = tuple(entry.byte_range) if entry.byte_range is not None else None
        consumer = ArrayBufferConsumer(
            entry, obj_out, fut, verify_location=logical_path
        )
        read_reqs = [
            ReadReq(
                path=entry.location,
                byte_range=byte_range,
                buffer_consumer=consumer,
                into=consumer.into_mv,
                # In place, or a whole blob into the plug-in's scratch
                # buffer: hashed where it is read. A member of a slab is
                # read as part of a spanning range and hashed in consume.
                want_crc=(consumer.into_mv is not None or byte_range is None)
                and _want_crc(entry),
                # A raw blob written whole is exactly its tensor's bytes.
                expected_nbytes=nbytes if byte_range is None else None,
                logical_path=logical_path,
            )
        ]
        return read_reqs, fut

    @staticmethod
    def _prepare_tiled_read(
        entry: TensorEntry,
        obj_out: Optional[ArrayLike],
        buffer_size_limit_bytes: int,
        fut: Future,
        logical_path: str = "",
    ) -> Tuple[List[ReadReq], Future]:
        """Split one tensor read into byte-ranged row tiles so peak host
        memory stays under the budget (reference tensor.py:126-179).

        The tiles are copied into one preallocated host array; the future
        resolves when the last tile lands. When the entry carries
        tile-grain checksums, read tiles are aligned to the recorded
        boundaries and each verified against the combined tile values —
        memory-budgeted reads detect corruption like whole-blob reads do.
        """
        shape = entry.shape
        row_nbytes = tensor_nbytes(entry.dtype, shape[1:]) if len(shape) > 1 else tensor_nbytes(entry.dtype, [1])
        rows_per_tile = max(1, buffer_size_limit_bytes // max(row_nbytes, 1))
        n_rows = shape[0]
        from ..knobs import is_checksum_disabled

        verify_tiles = (
            bool(entry.tile_checksums and entry.tile_rows)
            and not is_checksum_disabled()
        )
        if verify_tiles:
            if rows_per_tile >= entry.tile_rows:
                # Round down to a multiple of the checksum tile.
                rows_per_tile = (
                    rows_per_tile // entry.tile_rows
                ) * entry.tile_rows
            else:
                # Integrity over budget: the recorded tile is the minimum
                # verifiable read unit (16 MiB-class by default).
                rows_per_tile = entry.tile_rows

        # Preallocated host destination; tiles land in place.
        if isinstance(obj_out, np.ndarray) and (
            dtype_to_string(obj_out.dtype) == entry.dtype
            and list(obj_out.shape) == list(shape)
            and obj_out.flags.writeable
        ):
            host_out = obj_out
            in_place = True
        else:
            from ..serialization import string_to_dtype
            from .. import _native

            # Fresh multi-GB destination: fault as hugepages, not 4 KiB
            # pages — first-touch cost during the tile reads otherwise
            # rivals the I/O itself on few-core hosts.
            host_out = _native.empty_advised(shape, string_to_dtype(entry.dtype))
            in_place = False

        base_offset = entry.byte_range[0] if entry.byte_range is not None else 0
        n_tiles = math.ceil(n_rows / rows_per_tile)
        remaining = {"count": n_tiles}
        read_reqs = []
        for t in range(n_tiles):
            r0 = t * rows_per_tile
            r1 = min(r0 + rows_per_tile, n_rows)
            start = base_offset + r0 * row_nbytes
            end = base_offset + r1 * row_nbytes
            tile_checksum = (
                combined_tile_checksum(entry, r0, r1, row_nbytes)
                if verify_tiles
                else None
            )
            consumer = _TileConsumer(
                entry,
                host_out,
                r0,
                r1,
                remaining,
                fut,
                obj_out,
                in_place,
                blob_checksum=tile_checksum,
                blob_location=(
                    f"{logical_path or entry.location} (rows {r0}:{r1})"
                ),
            )
            read_reqs.append(
                ReadReq(
                    path=entry.location,
                    byte_range=(start, end),
                    buffer_consumer=consumer,
                    into=consumer.into_mv,
                    want_crc=consumer.into_mv is not None
                    and tile_checksum is not None,
                    logical_path=logical_path,
                )
            )
        return read_reqs, fut


    @staticmethod
    def _prepare_compressed_read(
        entry: TensorEntry,
        obj_out: Optional[ArrayLike],
        buffer_size_limit_bytes: Optional[int],
        fut: Future,
        logical_path: str = "",
    ) -> Tuple[List[ReadReq], Future]:
        """Read path for a codec entry: compressed tiles are read by
        byte range (grouped so each group's DECOMPRESSED bytes fit the
        memory budget — the stored tile is the random-access unit, so
        ``read_object`` and budget-tiled restores work at tile grain),
        verified against the combined compressed-tile checksum, then
        fused-decompressed (LZ4 + unshuffle, parallel across tiles)
        straight into the destination rows."""
        shape = entry.shape
        raw_nbytes = entry.uncompressed_nbytes or tensor_nbytes(
            entry.dtype, shape
        )
        sizes = [int(s) for s in (entry.comp_tile_sizes or [])]
        tile_rows = entry.tile_rows or 0
        n_rows = shape[0] if shape else 0
        row_nbytes = raw_nbytes // n_rows if n_rows else 0
        tile_raw = tile_rows * row_nbytes if tile_rows else raw_nbytes
        n_tiles = max(len(sizes), 1)
        if not sizes:
            raise IOError(
                f"compressed entry {entry.location!r} records no "
                "comp_tile_sizes — the snapshot metadata is inconsistent"
            )
        # The tile list must COVER the payload: each group below only
        # verifies its own range, so a truncated comp_tile_sizes (buggy
        # external rewriter) would otherwise "restore" with the tail of
        # the destination never written — every per-group checksum
        # green, result garbage.
        from ..compress import check_tile_coverage

        check_tile_coverage(entry.location, len(sizes), raw_nbytes, tile_raw)
        if isinstance(obj_out, np.ndarray) and (
            dtype_to_string(obj_out.dtype) == entry.dtype
            and list(obj_out.shape) == list(shape)
            and obj_out.flags.writeable
        ):
            host_out = obj_out
            in_place = True
        else:
            from .. import _native
            from ..serialization import string_to_dtype

            host_out = _native.empty_advised(
                shape, string_to_dtype(entry.dtype)
            )
            in_place = False
        dest_mv = array_as_memoryview(host_out)
        if dest_mv.readonly:  # zero-size arrays come back read-only
            dest_mv = None
        base = entry.byte_range[0] if entry.byte_range is not None else 0
        from ..compress import comp_tile_offsets

        offsets = comp_tile_offsets(sizes)
        # Group consecutive tiles while the group's decompressed bytes
        # fit the budget (>= 1 tile per group: the stored tile is the
        # minimum readable unit, integrity over budget — same policy as
        # the uncompressed tiled read).
        groups: List[Tuple[int, int]] = []
        t0 = 0
        while t0 < n_tiles:
            t1 = t0 + 1
            if buffer_size_limit_bytes is not None:
                while (
                    t1 < n_tiles
                    and (t1 + 1 - t0) * tile_raw <= buffer_size_limit_bytes
                ):
                    t1 += 1
            else:
                t1 = n_tiles
            groups.append((t0, t1))
            t0 = t1
        remaining = {"count": len(groups)}
        from ..compress import combined_comp_checksum
        from ..knobs import is_checksum_disabled

        verify = not is_checksum_disabled()
        read_reqs: List[ReadReq] = []
        for g0, g1 in groups:
            comp_start = base + offsets[g0]
            comp_end = base + offsets[g1 - 1] + sizes[g1 - 1]
            expected = (
                combined_comp_checksum(entry, g0, g1) if verify else None
            )
            raw_start = g0 * tile_raw
            raw_end = min(g1 * tile_raw, raw_nbytes)
            consumer = _CompressedConsumer(
                entry=entry,
                dest_slice=(
                    dest_mv[raw_start:raw_end] if dest_mv is not None else None
                ),
                comp_sizes=sizes[g0:g1],
                tile_raw=tile_raw,
                raw_len=raw_end - raw_start,
                remaining=remaining,
                fut=fut,
                host_out=host_out,
                obj_out=obj_out,
                in_place=in_place,
                expected_checksum=expected,
                location=(
                    f"{logical_path or entry.location} "
                    f"(comp tiles {g0}:{g1})"
                ),
            )
            read_reqs.append(
                ReadReq(
                    path=entry.location,
                    byte_range=(comp_start, comp_end),
                    buffer_consumer=consumer,
                    want_crc=expected is not None,
                    logical_path=logical_path,
                )
            )
        return read_reqs, fut


class _CompressedConsumer(BufferConsumer):
    """Consumes one group of compressed tiles: verify the CRC of the
    stored bytes (the fused read-time value when the plugin computed
    one, else one hash pass), then fused-decompress into the
    destination rows. Completion bookkeeping mirrors _TileConsumer."""

    def __init__(
        self,
        entry: TensorEntry,
        dest_slice: Optional[memoryview],
        comp_sizes: List[int],
        tile_raw: int,
        raw_len: int,
        remaining: dict,
        fut: Future,
        host_out,
        obj_out,
        in_place: bool,
        expected_checksum: Optional[str],
        location: str,
    ) -> None:
        self.entry = entry
        self.dest_slice = dest_slice
        self.comp_sizes = comp_sizes
        self.tile_raw = tile_raw
        self.raw_len = raw_len
        self.remaining = remaining
        self.fut = fut
        self.host_out = host_out
        self.obj_out = obj_out
        self.in_place = in_place
        self.expected_checksum = expected_checksum
        self.location = location
        self.comp_nbytes = sum(comp_sizes)
        # Decode-lane attribution: capture the restore's recorder at
        # construction (prepare_read runs under the restore's telemetry
        # overlay); _consume_blocking later runs on a consume-executor
        # thread, where the thread-local overlay is invisible.
        # record_span is lock-guarded, so recording from that thread is
        # safe.
        from .. import telemetry as _telemetry

        self._tele = _telemetry.current()

    async def consume_read_io(self, read_io, executor: Optional[Executor] = None) -> None:
        buf = read_io.buf.getbuffer()
        if executor is not None:
            await _consume_handoff(
                executor, self._consume_blocking, buf, read_io.crc32c, read_io.crc_algo
            )
        else:
            self._consume_blocking(buf, read_io.crc32c, read_io.crc_algo)
        await self._after_consume(executor)

    async def consume_buffer(
        self, buf: BufferType, executor: Optional[Executor] = None
    ) -> None:
        if executor is not None:
            await _consume_handoff(executor, self._consume_blocking, buf, None, None)
        else:
            self._consume_blocking(buf, None, None)
        await self._after_consume(executor)

    def _consume_blocking(self, buf: BufferType, crc, crc_algo) -> None:
        with telemetry.span("decode", rec=self._tele, bytes=self.comp_nbytes):
            self._verify_and_decompress(buf, crc, crc_algo)

    def _verify_and_decompress(self, buf: BufferType, crc, crc_algo) -> None:
        from .. import _native
        from ..knobs import get_native_copy_threads

        mv = memoryview(buf).cast("B")
        if self.expected_checksum is not None:
            if crc is not None and crc_algo:
                # Fused read-time hash: verify a 4-byte value, no
                # second pass over the compressed bytes.
                _native.verify_checksum_value(
                    crc, crc_algo, self.expected_checksum, self.location
                )
            else:
                _native.verify_checksum(
                    mv, self.expected_checksum, self.location
                )
        if mv.nbytes != self.comp_nbytes:
            raise IOError(
                f"short read: got {mv.nbytes} of {self.comp_nbytes} "
                f"compressed bytes for {self.location} — the blob is "
                "truncated"
            )
        if self.dest_slice is None:
            return  # zero-size destination: nothing to decode
        from ..compress import codec_elem

        tele = self._tele
        start = tele.now() if tele is not None else 0.0
        try:
            # One span site covers native decode AND the Python
            # fallback — the fallback lives inside decompress_tiles.
            _native.decompress_tiles(
                mv,
                self.comp_sizes,
                self.tile_raw,
                self.raw_len,
                codec_elem(self.entry.codec),
                self.dest_slice,
                nthreads=get_native_copy_threads(),
            )
            if tele is not None:
                tele.record_span(
                    "restore.decode",
                    start,
                    tele.now() - start,
                    kind="work",
                    path=self.location,
                    bytes=self.comp_nbytes,
                    raw_bytes=self.raw_len,
                )
        except _native.CompressionError as e:
            raise _native.CompressionError(
                f"{self.location}: {e} (stored checksum verified — the "
                "blob was written malformed, not corrupted in transit)"
            ) from e

    async def _after_consume(self, executor: Optional[Executor] = None) -> None:
        self.remaining["count"] -= 1
        if self.remaining["count"] != 0:
            return
        if self.in_place:
            self.fut.obj = self.host_out
            return
        if executor is not None:
            self.fut.obj = await _consume_handoff(
                executor, finalize_into_target, self.host_out, self.obj_out, True
            )
        else:
            self.fut.obj = finalize_into_target(
                self.host_out, self.obj_out, True
            )

    def get_consuming_cost_bytes(self) -> int:
        return self.raw_len + self.comp_nbytes


class _TileConsumer(BufferConsumer):
    def __init__(
        self,
        entry,
        host_out,
        r0,
        r1,
        remaining,
        fut,
        obj_out,
        in_place,
        blob_checksum=None,
        blob_location="",
    ):
        self.entry = entry
        self.host_out = host_out
        self.r0, self.r1 = r0, r1
        self.remaining = remaining
        self.fut = fut
        self.obj_out = obj_out
        self.in_place = in_place
        # The checksum this read range is verifiable against: the chunk's
        # whole-blob value for chunked reads, or the combined tile value
        # for budget tiles aligned to recorded checksum-tile boundaries
        # (None when the range is unverifiable or verification is off).
        self.blob_checksum = blob_checksum
        self.blob_location = blob_location
        # The tile's destination rows are contiguous in host_out, so the
        # read may land there directly (host_out is freshly allocated or
        # already validated as an exact-match target).
        row_slice = host_out[self.r0 : self.r1]
        mv = (
            array_as_memoryview(row_slice)
            if row_slice.flags.c_contiguous and row_slice.flags.writeable
            else None
        )
        # Zero-byte slices come back as a read-only memoryview(b"").
        self.into_mv = mv if mv is not None and not mv.readonly else None

    async def consume_read_io(self, read_io, executor: Optional[Executor] = None) -> None:
        if read_io.in_place:
            if self.blob_checksum is not None and read_io.crc32c is not None:
                from .. import _native

                _native.verify_checksum_value(
                    read_io.crc32c,
                    read_io.crc_algo,
                    self.blob_checksum,
                    self.blob_location,
                )
        else:
            await self.consume_buffer(read_io.buf.getbuffer(), executor)
            return
        await self._after_consume(executor)

    async def consume_buffer(
        self, buf: BufferType, executor: Optional[Executor] = None
    ) -> None:
        if executor is not None:
            await _consume_handoff(executor, self._consume_blocking, buf)
        else:
            self._consume_blocking(buf)
        await self._after_consume(executor)

    async def _after_consume(self, executor: Optional[Executor] = None) -> None:
        # Completion bookkeeping stays on the event-loop thread — the
        # executor runs up to 4 consumers concurrently and a bare
        # read-modify-write there can lose decrements.
        self.remaining["count"] -= 1
        if self.remaining["count"] != 0:
            return
        if self.in_place:
            # host_out IS the caller's target; bytes already landed.
            self.fut.obj = self.host_out
            return
        # Finalization may be a full data pass (cast into a
        # mismatched-dtype target) — run it in the executor so the
        # event loop keeps dispatching other entries' reads.
        if executor is not None:
            self.fut.obj = await _consume_handoff(
                executor, finalize_into_target, self.host_out, self.obj_out, True
            )
        else:
            self.fut.obj = finalize_into_target(
                self.host_out, self.obj_out, True
            )

    def _consume_blocking(self, buf: BufferType) -> None:
        with telemetry.span("decode", bytes=memoryview(buf).nbytes):
            _maybe_verify(buf, self.blob_checksum, self.blob_location)
            tile_shape = [self.r1 - self.r0] + list(self.entry.shape[1:])
            src = array_from_memoryview(
                memoryview(buf), self.entry.dtype, tile_shape
            )
            np.copyto(self.host_out[self.r0 : self.r1], src)

    def get_consuming_cost_bytes(self) -> int:
        return tensor_nbytes(
            self.entry.dtype, [self.r1 - self.r0] + list(self.entry.shape[1:])
        )
