"""Snapshot — the user-facing save/restore/random-access API.

TPU-native counterpart of /root/reference/torchsnapshot/snapshot.py.
Preserved semantics (call stacks in SURVEY.md §3):

- ``take``: coalesce path/replicated globs across ranks → per-key
  ``state_dict()`` in a globally agreed order (with barriers so statefuls
  that run collectives inside ``state_dict`` can't interleave,
  reference :352-368) → flatten → prepare write requests → replicated
  write dedup/partitioning → gather + merge per-rank manifests into a
  global manifest keyed ``rank/logical_path`` (reference :842-853) →
  budget-gated pipelined execution → two-phase commit: rank 0 writes
  ``.snapshot_metadata`` only after every rank finished writing
  (reference :227-234).
- ``async_take``: control returns at FIRST-WINDOW-STAGED — a
  memory-budget-bounded window of write requests is staged on the
  calling thread (everything, when the state fits
  TPUSNAP_ASYNC_STAGE_WINDOW_BYTES — then the pre-pipeline
  staging-complete semantics hold exactly); residual staging windows,
  storage I/O and the commit happen on a background thread that
  coordinates via a KV-store LinearBarrier — never collectives
  (reference :856-944). ``PendingSnapshot.wait_staged()`` is the
  staging-complete rendezvous for callers that mutate host-aliasing
  state in place.
- ``restore``: per-key global order; per-rank manifest view with
  replicated re-expansion and sharded merge; reads scattered/reassembled
  into the target sharding; RNG state restored last (reference :437-481).
- ``read_object``: random access to one object under a memory budget
  (reference :501-594).

TPU-first deltas: replication is **inferred from shardings** — a
fully-replicated multi-process ``jax.Array`` is provably identical on
every rank, so the sharded preparer's replica-0 dedup stores one copy
automatically without the reference's DDP-module introspection
(snapshot.py:791-807); the glob API is kept for host-side values
(numpy arrays, primitives) where no sharding exists.
"""

from __future__ import annotations

import asyncio
import fnmatch
import functools
import logging
import threading
import uuid
from typing import Any, Dict, List, Optional, Set

import jax

from . import access, telemetry
from .comm import Communicator, get_communicator
from .dist_store import (
    CoordinationKVStore,
    KVStore,
    LinearBarrier,
    MemoryKVStore,
    TakeAbortedError,
    TakeAbortMonitor,
)
from .flatten import flatten, inflate
from .io_preparer import prepare_read, prepare_write
from .liveness import (
    LeasePublisher,
    LivenessMonitor,
    RankFailedError,
)
from .io_types import ReadIO, StoragePlugin, WriteIO
from .manifest import (
    Entry,
    Manifest,
    SnapshotMetadata,
    is_container_entry,
    is_replicated,
)
from .manifest_ops import get_manifest_for_rank, handle_sharded_elasticity
from .rng_state import RNGState
from .scheduler import (
    PendingIOWork,
    get_process_memory_budget_bytes,
    sync_execute_read_reqs,
    sync_execute_write_reqs,
)
from .stateful import AppState, Stateful
from .storage_plugin import url_to_storage_plugin_in_event_loop
from .version import __version__

logger = logging.getLogger(__name__)

SNAPSHOT_METADATA_FNAME = ".snapshot_metadata"


class Snapshot:
    """Handle on a snapshot. The event loop and storage plugin are
    created lazily on first use and REUSED across restore/read_object/
    metadata calls (a GCS plugin holds an authorized session — paying
    its construction per ``read_object`` in a loop is pure overhead;
    the reference rebuilds both per call, snapshot.py:437-520). Call
    ``close()`` (or use the handle as a context manager) to release
    them; they are also re-created transparently after a close."""

    def __init__(
        self,
        path: str,
        storage_options: Optional[Dict[str, Any]] = None,
        comm: Optional[Communicator] = None,
    ) -> None:
        self.path = path
        self._storage_options = storage_options
        self._comm = comm
        self._metadata: Optional[SnapshotMetadata] = None
        self._cached_loop: Optional[asyncio.AbstractEventLoop] = None
        self._cached_storage: Optional[StoragePlugin] = None
        # restore/read_object/metadata serialize on this lock: they share
        # the cached loop, and a second run_until_complete on a running
        # loop raises. Threads wanting concurrent reads use separate
        # Snapshot handles (each carries its own loop + plugin).
        self._op_lock = threading.RLock()

    def _resources(self):
        """(event_loop, storage), cached across calls. Callers hold
        ``_op_lock`` for the duration of their use."""
        if self._cached_loop is None or self._cached_loop.is_closed():
            self._cached_loop = asyncio.new_event_loop()
            self._cached_storage = None
        if self._cached_storage is None:
            self._cached_storage = url_to_storage_plugin_in_event_loop(
                self.path, self._cached_loop, self._storage_options
            )
        return self._cached_loop, self._cached_storage

    def close(self) -> None:
        """Release the cached storage plugin and event loop."""
        self._close(blocking=True)

    def _close(self, blocking: bool) -> None:
        # The finalizer path (__del__) must NOT block on _op_lock: GC
        # can fire on a thread that holds arbitrary locks (e.g. the
        # executor's shutdown locks inside submit), and blocking there
        # while another snapshot's op holds ITS _op_lock and submits is
        # one unlucky schedule from an AB/BA deadlock — the lockwatch
        # watchdog flagged exactly this edge. A contended _op_lock from
        # __del__ means the object is still in use; skipping the close
        # leaks nothing (the next explicit close or GC pass retries).
        if not self._op_lock.acquire(blocking):
            return
        try:
            # GC may run __del__ from inside another running event loop
            # (e.g. while a different snapshot's coroutines execute);
            # run_until_complete is illegal there, so skip the graceful
            # storage close and only drop references.
            try:
                asyncio.get_running_loop()
                in_async_context = True
            except RuntimeError:
                in_async_context = False
            if (
                not in_async_context
                and self._cached_storage is not None
                and self._cached_loop is not None
                and not self._cached_loop.is_closed()
                and not self._cached_loop.is_running()
            ):
                try:
                    self._cached_storage.sync_close(self._cached_loop)
                except Exception:
                    pass
            self._cached_storage = None
            if self._cached_loop is not None:
                try:
                    if not self._cached_loop.is_running():
                        if not blocking:
                            # Finalizer path: loop.close() — here or in
                            # asyncio's own __del__ if we cannot close —
                            # shuts down the loop's DEFAULT executor
                            # (run_in_executor(None, ...), the read-abort
                            # drain uses it) with a BLOCKING
                            # _shutdown_lock acquire, the exact GC-inside-
                            # submit AB/BA window shutdown_plugin_executor
                            # documents. Detach it and trylock-shutdown
                            # instead (we are inside finalizer_close_scope,
                            # so the helper takes the no-wait branch).
                            self._detach_default_executor(self._cached_loop)
                        self._cached_loop.close()
                except Exception:
                    pass
            self._cached_loop = None
        finally:
            self._op_lock.release()

    @staticmethod
    def _detach_default_executor(loop) -> None:
        from .io_types import shutdown_plugin_executor

        try:
            executor = loop._default_executor
            if executor is None:
                return
            loop._default_executor = None
        except Exception:
            return
        shutdown_plugin_executor(executor)

    def __enter__(self) -> "Snapshot":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __del__(self) -> None:
        # Best-effort: `Snapshot(path).restore(...)` temporaries are
        # refcount-collected at statement end, so the common drop-the-
        # handle pattern releases its loop and storage promptly without
        # an explicit close(). Finalizer scope: plugin close() must not
        # join threads here — GC can fire inside a starting thread's
        # Thread._set_tstate_lock, where a join self-deadlocks on
        # threading._shutdown_locks_lock (io_types.finalizer_close_scope).
        from .io_types import finalizer_close_scope

        try:
            with finalizer_close_scope():
                self._close(blocking=False)
        except Exception:
            pass

    # ------------------------------------------------------------------ take

    @classmethod
    def take(
        cls,
        path: str,
        app_state: AppState,
        replicated: Optional[List[str]] = None,
        storage_options: Optional[Dict[str, Any]] = None,
        comm: Optional[Communicator] = None,
        per_key_barrier: bool = False,
        incremental_from: Optional[str] = None,
        _custom_array_prepare_func: Optional[Any] = None,
        _extras: Optional[Dict[str, Any]] = None,
        _record_dedup_hashes: bool = False,
    ) -> "Snapshot":
        """``_custom_array_prepare_func(logical_path, arr, tracing)``
        transforms dense, chunked and sharded arrays at save time
        (dtype cast / quantize-on-save; reference
        _custom_tensor_prepare_func, snapshot.py:170-196; threaded into
        the sharded path like reference io_preparer.py:100-106). At
        prepare time it is traced abstractly (``jax.eval_shape`` — zero
        FLOPs) to learn the stored dtype/shape; at stage time it runs
        for real, per local shard for sharded arrays. It must not
        change the shape, and must be deterministic.

        ``incremental_from`` makes this an INCREMENTAL snapshot against a
        previous one at that path (same scheme/bucket; typically a
        sibling directory): any blob whose staged bytes hash to the same
        stage-time checksums (whole-blob + tile-grain CRCs, plus matching
        dtype/shape/box) skips its storage write, and the new manifest
        references the previous snapshot's blob by relative location.
        The result is self-describing and restores/scrubs/read_objects
        like any snapshot — but it REQUIRES the base snapshot(s) to stay
        alive; deleting a base breaks the snapshots layered on it
        (``python -m tpusnap verify`` reports the dangling references).
        Dedup is fine-grained: slab-batched small arrays dedup per
        member (the new slab holds only changed members), and a large
        array whose base entry carries per-tile dedup hashes rewrites
        only its CHANGED checksum tiles — one changed row of a multi-GB
        array costs one tile, with unchanged tiles stored as byte-range
        references into the base blob. Every skip decision requires a
        32-bit CRC AND an independent 64-bit hash to match. Tile-grain
        skips need the PREVIOUS entry to carry per-tile dedup hashes,
        which incremental takes record whenever they WRITE a blob — so
        in a chain, each blob reaches tile grain one take after it
        first rewrites (its unchanged takes skip whole-blob on the
        CRC-only pass). Set TPUSNAP_RECORD_DEDUP_HASHES=1 on the full
        base take to give every blob tile grain from the first
        increment. Pass the same value on every rank.

        ``per_key_barrier=True`` restores the reference's barrier
        between every stateful's ``state_dict()`` call (snapshot.py:
        362-368) — needed only when a stateful runs its own collectives
        inside ``state_dict`` and those must not interleave across keys.
        tpusnap itself issues no device collectives during take, so the
        default skips the barriers (and their extra key gather)."""
        comm = get_communicator(comm)
        event_loop = asyncio.new_event_loop()
        abort_ctx = _TakeAbortContext(comm)
        abort_ctx.event_loop = event_loop
        tele = telemetry.begin_take(comm.rank)
        try:
            (
                pending_io_work,
                metadata,
                path,
                storage,
                late_checksums,
                tele_commit,
            ) = _take_impl(
                path=path,
                app_state=app_state,
                storage_options=storage_options,
                comm=comm,
                replicated=replicated or [],
                event_loop=event_loop,
                is_async_snapshot=False,
                per_key_barrier=per_key_barrier,
                array_prepare_func=_custom_array_prepare_func,
                incremental_from=incremental_from,
                abort_ctx=abort_ctx,
                extras=_extras,
                force_dedup_hashes=_record_dedup_hashes,
            )
            drain_start = tele.now()
            pending_io_work.sync_complete(event_loop)
            # The residual-I/O window: storage writes draining after
            # staging completed.
            tele.record_span(
                "io_drain", drain_start, tele.now() - drain_start, phase=True
            )
            prep_start = tele.now()
            from .knobs import is_durable_commit_enabled

            if is_durable_commit_enabled():
                # Every rank makes its own dirents durable before the
                # commit barrier — rank 0's metadata fsync can only
                # cover directories ITS plugin instance created.
                storage.sync_flush_created_dirs(event_loop)
            if late_checksums is not None:
                # Writes drained: this rank's deferred checksums are
                # final — publish before the barrier; rank 0 applies
                # after it (every rank arrived ⟹ every rank published).
                late_checksums.publish()
            # Writes drained: freeze + persist this rank's trace inside
            # the snapshot and publish its summary — BEFORE the commit
            # barrier, preserving metadata-written-last.
            tele_commit.persist(storage, event_loop, abort_ctx, prep_start)
            # With the abort watcher armed (multi-process), both commit
            # barriers poll for peer abort records and raise
            # TakeAbortedError within seconds instead of burning the
            # full barrier timeout on a failed rank.
            comm.barrier()
            # Barrier passed ⟹ every rank published: EVERY rank patches
            # its local manifest copy (late checksums) and folds the
            # telemetry rollup. Rank 0's patch is load-bearing (it
            # writes the file); a non-leader patch failure falls back
            # to the lazy committed-file read (ADVICE r5 #4).
            meta_cached = True
            try:
                if late_checksums is not None:
                    late_checksums.apply(metadata.manifest)
                if not tele_commit.apply(metadata):
                    # Non-leader KV read came back incomplete: its copy
                    # would diverge from the committed rollup — drop the
                    # cache, keep the take.
                    meta_cached = False
            except Exception:
                if comm.rank == 0:
                    raise
                logger.warning(
                    "Non-leader late-checksum patch failed; falling back "
                    "to reading committed metadata (non-fatal)",
                    exc_info=True,
                )
                meta_cached = False
            if comm.rank == 0:
                abort_ctx.mark_commit_started()
                _write_metadata(storage, metadata, event_loop)
            # The second commit barrier doubles as the cleanup gate:
            # every rank passing it has read the take-scoped KV blobs,
            # so rank 0 can delete them after it.
            comm.barrier()
            if comm.rank == 0:
                if late_checksums is not None:
                    late_checksums.cleanup()
                tele_commit.cleanup()
            # Commit is definitive: mark the take completed (end_take
            # publishes only completed takes to the cross-run history),
            # anchor the SLO tracker (RPO clock restarts, data-at-risk
            # clears), publish the final heartbeat (100%) and stop the
            # pump before the handle is returned.
            tele.meta["completed"] = True
            _record_slo_commit(
                tele, metadata, tele_commit.take_id, path, comm.rank
            )
            tele_commit.finish_progress()
            # Final black-box flush with the committed verdict (the
            # pump's last tick already flushed; this one is forced and
            # carries the take_end event). Never raises.
            from . import flight as _flight_mod

            _flight_mod.recorder().end_take("committed")
            if comm.rank == 0:
                # Metadata committed and every rank departed: the take
                # journal's job is done. Best-effort — a crash before
                # this clear leaves valid metadata + a stale journal,
                # which fsck classifies as committed (gc reclaims the
                # leftovers). Cleared strictly AFTER the metadata write,
                # preserving metadata-written-last.
                from .knobs import is_journal_disabled
                from .lifecycle import clear_journal

                if not is_journal_disabled():
                    clear_journal(
                        storage,
                        event_loop,
                        getattr(storage, "clear_world_size", comm.world_size),
                    )
                if abort_ctx.monitor is not None:
                    abort_ctx.monitor.clear()
                if abort_ctx.lease is not None:
                    abort_ctx.lease.cleanup()
            storage.sync_close(event_loop)
        except RankFailedError as rank_exc:
            # A peer died mid-take. Under TPUSNAP_RANK_FAILURE=degrade
            # (and with the recovery context armed — post-staging,
            # non-incremental), the survivors complete a
            # replicated-only take without it; anything else aborts to
            # a torn state exactly like any failure, with the dead
            # rank named by the flight breadcrumbs.
            try:
                degraded_meta = _maybe_degraded_commit(abort_ctx, rank_exc)
            except BaseException as e:
                abort_ctx.on_failure(e)
                raise
            if degraded_meta is None:
                abort_ctx.on_failure(rank_exc)
                raise
            metadata = degraded_meta
            meta_cached = True  # every survivor built the same manifest
            tele.meta["completed"] = True
            _record_slo_commit(
                tele, metadata, abort_ctx.degrade.take_id, path, comm.rank
            )
            if abort_ctx.progress is not None:
                try:
                    abort_ctx.progress.finish("committed")
                except Exception:
                    pass
            from . import flight as _flight_mod

            _flight_mod.recorder().end_take("committed")
            abort_ctx.degrade.storage.sync_close(
                abort_ctx.degrade.event_loop
            )
        except BaseException as e:
            abort_ctx.on_failure(e)
            raise
        finally:
            # Safety net: on any exit path the pump thread must be gone
            # (on_failure/finish_progress already stopped it; idempotent).
            if abort_ctx.progress is not None:
                abort_ctx.progress.stop()
            telemetry.end_take(tele)
            abort_ctx.disarm()
            event_loop.close()
        snapshot = cls(path, storage_options, comm)
        if meta_cached:
            # Every rank's copy is fully patched (late checksums + the
            # telemetry rollup applied locally from the KV blobs), so
            # every rank caches it — no per-rank metadata GET against
            # cloud storage on first access. The rare non-leader patch
            # failure leaves the handle uncached; its first metadata
            # access reads the committed file rank 0 wrote.
            snapshot._metadata = metadata
        return snapshot

    @classmethod
    def async_take(
        cls,
        path: str,
        app_state: AppState,
        replicated: Optional[List[str]] = None,
        storage_options: Optional[Dict[str, Any]] = None,
        comm: Optional[Communicator] = None,
        per_key_barrier: bool = False,
        incremental_from: Optional[str] = None,
        _custom_array_prepare_func: Optional[Any] = None,
        _extras: Optional[Dict[str, Any]] = None,
        _record_dedup_hashes: bool = False,
        _force_clone_staging: bool = False,
        _stream_capture: bool = False,
    ) -> "PendingSnapshot":
        comm = get_communicator(comm)
        event_loop = asyncio.new_event_loop()
        abort_ctx = _TakeAbortContext(comm)
        abort_ctx.event_loop = event_loop
        tele = telemetry.begin_take(comm.rank)
        try:
            (
                pending_io_work,
                metadata,
                path,
                storage,
                late_checksums,
                tele_commit,
            ) = _take_impl(
                path=path,
                app_state=app_state,
                storage_options=storage_options,
                comm=comm,
                replicated=replicated or [],
                event_loop=event_loop,
                is_async_snapshot=True,
                per_key_barrier=per_key_barrier,
                array_prepare_func=_custom_array_prepare_func,
                incremental_from=incremental_from,
                abort_ctx=abort_ctx,
                extras=_extras,
                force_dedup_hashes=_record_dedup_hashes,
                force_clone_staging=_force_clone_staging,
                stream_capture=_stream_capture,
            )
            # Control returns to training here: the blocked window is
            # over — of the requests whose bytes the caller could write
            # in place the first staging window is staged (all of them
            # when they fit TPUSNAP_ASYNC_STAGE_WINDOW_BYTES; ALL
            # staging when the take is incremental or the window 0);
            # accelerator-resident leaves, held by reference, and the
            # residual windows are staged on the background drain,
            # interleaved with storage I/O. Callers that mutate
            # host-aliasing state IN PLACE, and steps that DONATE their
            # state, synchronize on wait_staged(); functional JAX
            # updates that donate nothing never need to.
            return PendingSnapshot(
                path=path,
                pending_io_work=pending_io_work,
                metadata=metadata,
                storage=storage,
                comm=comm,
                event_loop=event_loop,
                storage_options=storage_options,
                late_checksums=late_checksums,
                abort_ctx=abort_ctx,
                tele_commit=tele_commit,
            )
        except BaseException as e:
            telemetry.end_take(tele)
            abort_ctx.on_failure(e)
            abort_ctx.disarm()
            event_loop.close()
            raise

    # ---------------------------------------------------------------- stream

    @classmethod
    def stream(
        cls,
        root: str,
        app_state: AppState,
        cadence_s: Optional[float] = None,
        replicated: Optional[List[str]] = None,
        storage_options: Optional[Dict[str, Any]] = None,
        comm: Optional[Communicator] = None,
        max_chain: Optional[int] = None,
    ) -> "DeltaStream":
        """Continuous delta checkpointing: open a :class:`~tpusnap.delta.
        DeltaStream` under ``root`` — a full base snapshot now, then one
        journaled incremental micro-commit per ``cadence_s`` (default
        ``TPUSNAP_DELTA_CADENCE_S``) shipping only tiles/blobs whose
        fresh CRC32C+XXH64 pair differs from the last committed
        increment. A crash at any instant recovers, via base + committed
        delta chain, to a state no older than ~one cadence interval
        (``tpusnap.delta.resolve_chain(root).head`` names the recovery
        head; ``Snapshot(head).restore`` replays it transparently).
        ``close()`` the stream (or use it as a context manager) to stop.
        See :mod:`tpusnap.delta` for the step-consistency contract
        (``mark_step``/``commit_now``) and chain compaction."""
        from .delta import DeltaStream

        return DeltaStream(
            root,
            app_state,
            cadence_s=cadence_s,
            replicated=replicated,
            storage_options=storage_options,
            comm=comm,
            max_chain=max_chain,
        )

    # --------------------------------------------------------------- restore

    def restore(self, app_state: AppState, per_key_barrier: bool = False) -> None:
        """Each rank restores its own manifest view independently — the
        default restore issues no barriers and no per-key collectives
        (the snapshot is immutable and every rank reads storage
        directly; the reference barriers once per key,
        snapshot.py:459-470, which at 16+ processes x many keys is pure
        serial KV overhead). The one exception: a fresh process gathers
        hostnames ONCE to size the memory budget (cached thereafter; a
        take in the same process pre-populates it) — so all ranks must
        enter a cold restore together, as they do on any SPMD restart.

        ``per_key_barrier=True`` restores the reference's global key
        order + barrier-per-key — needed only when a stateful runs its
        own collectives inside ``load_state_dict``."""
        comm = get_communicator(self._comm)
        _validate_app_state(app_state)
        with self._op_lock:
            self._restore_locked(app_state, comm, per_key_barrier)

    def async_restore(self, app_state: AppState) -> "PendingRestore":
        """Restore on a background thread; training-adjacent work
        (compilation, data pipeline warmup) overlaps the storage reads.
        ``app_state``'s statefuls must not be touched until ``wait()``
        returns — ``load_state_dict`` runs on the background thread.

        Safe off the main thread because the default restore issues NO
        collectives: the one cold-start collective (the memory-budget
        hostname gather) is taken HERE, on the calling thread, before
        the thread starts. ``per_key_barrier`` restores are inherently
        collective and have no async form (beyond the reference, which
        has no async restore either) — a stateful whose
        ``load_state_dict`` runs device collectives must declare
        ``load_requires_collectives = True`` (see ``Stateful``) and is
        REJECTED here: running its collectives from this background
        thread, unordered against other ranks, deadlocks or corrupts
        (the reference bans collectives off-thread the same way,
        snapshot.py:902)."""
        comm = get_communicator(self._comm)
        _validate_app_state(app_state)
        offenders = sorted(
            key
            for key, stateful in app_state.items()
            if getattr(stateful, "load_requires_collectives", False)
        )
        if offenders:
            raise ValueError(
                f"async_restore cannot restore {offenders}: their "
                "load_state_dict declares load_requires_collectives=True, "
                "and collectives must not run on the background restore "
                "thread (unordered across ranks -> deadlock/corruption). "
                "Use restore(per_key_barrier=True) for these statefuls."
            )
        # Cold-start collective on the calling thread; cached afterwards.
        memory_budget = get_process_memory_budget_bytes(comm)
        return PendingRestore(self, app_state, comm, memory_budget)

    def _restore_locked(
        self, app_state, comm, per_key_barrier, memory_budget=None, caller=None
    ) -> None:
        # Restore telemetry: a dedicated recorder (thread-local overlay,
        # so an in-flight take's global recorder is never disturbed)
        # with contiguous phases (restore.plan → per-key targets/
        # prepare/read/load) and the scheduler's storage_read/consume op
        # spans. The snapshot is immutable, so the trace persists to
        # the LOCAL trace dir (TPUSNAP_TELEMETRY_DIR) — rendered by
        # `python -m tpusnap trace --restore <path>`.
        # `caller`: the thread that called async_restore, where this is
        # its background thread; a watched restore samples it, and the
        # thread that runs the loop, from here on.
        tele = telemetry.begin_restore(comm.rank, caller=caller)
        tele.watch_begin()
        tele.meta.update(path=self.path, world_size=comm.world_size)
        mark = telemetry.PhaseMarker(rec=tele, from_start=True)
        mark.begin("restore.plan")
        # Access-ledger scope around the whole read path: every ReadReq
        # the restore executes attributes (logical path, byte range,
        # source tier) to this reader's sidecar — the raw material for
        # `tpusnap heatmap`. Opened manually (not read_scope) so the
        # disk flush waits until the telemetry wall has closed below;
        # otherwise it reads as unspanned restore time on tiny loads.
        ledger = access.open_ledger(
            self.path, default_source=self._access_default_source()
        )
        try:
            with telemetry.use(tele):
                with access.use(ledger):
                    self._restore_instrumented(
                        app_state, comm, per_key_barrier, memory_budget, mark
                    )
            # Only a restore that ran to completion becomes a history
            # trend point; the summary itself still publishes either way.
            tele.meta["completed"] = True
        finally:
            if ledger is not None:
                # In-memory totals only: the summary needs the access_*
                # fields, but the flush and fleet publish happen after
                # finalize() so they stay outside the measured wall.
                tele.meta["access"] = {
                    "bytes_read": ledger.total_bytes,
                    "reads": ledger.total_reads,
                    "working_set_bytes": ledger.working_set_bytes(),
                }
            # The tuned overlay is scoped to the operation that applied
            # it — knob reads after the restore see the plain env again.
            from .knobs import clear_tuned_plan

            clear_tuned_plan()
            tele.close()
            summary = tele.summary()
            telemetry.publish_restore_summary(summary)
            if tele.enabled:
                try:
                    from .progress import persist_restore_trace

                    persist_restore_trace(tele, self.path)
                except Exception:
                    logger.warning(
                        "Failed to persist restore trace (non-fatal)",
                        exc_info=True,
                    )
            if ledger is not None:
                try:
                    ledger.flush()
                except Exception:
                    logger.debug(
                        "access ledger flush failed", exc_info=True
                    )
                self._publish_access_stats(ledger)

    def _restore_instrumented(
        self, app_state, comm, per_key_barrier, memory_budget, mark
    ) -> None:
        event_loop, storage = self._resources()
        try:
            from .storage_plugin import storage_plugin_label

            # Which backend this restore reads from (tier-aware): the
            # history event's `plugin` field, what the SLO RTO
            # estimator filters its baseline on.
            telemetry.current().meta["plugin"] = storage_plugin_label(storage)
        except Exception:
            pass
        # Auto-tuner reconcile (TPUSNAP_AUTOTUNE=1): install this
        # cell's plan BEFORE the budget/knob reads below, so the
        # restore runs with the tuned values; the applied subset rides
        # the summary into the history event for attribution.
        from . import tune as _tune

        tuned = _tune.maybe_apply(
            "restore", storage=storage, world_size=comm.world_size
        )
        if tuned:
            telemetry.current().meta["tuned"] = tuned
        metadata = self._get_metadata(storage, event_loop)
        if memory_budget is None:
            memory_budget = get_process_memory_budget_bytes(comm)

        multi = comm.world_size > 1
        if per_key_barrier and multi:
            keys = _gather_keys(comm, sorted(app_state.keys()))
        else:
            keys = sorted(app_state.keys())
        # Metadata read/decode + budget + (optional) key gather.
        mark("restore.plan")  # each key begins its own phases
        # RNG state is restored last so that loading other statefuls
        # cannot perturb it (reference snapshot.py:473-481).
        rng_keys = [
            k for k in keys if isinstance(app_state.get(k), RNGState)
        ]
        for key in [k for k in keys if k not in rng_keys] + rng_keys:
            if per_key_barrier and multi:
                comm.barrier()
            stateful = app_state.get(key)
            if stateful is None:
                continue
            _load_stateful(
                stateful=stateful,
                key=key,
                metadata=metadata,
                rank=comm.rank,
                storage=storage,
                memory_budget=memory_budget,
                event_loop=event_loop,
                mark=mark,
            )

    # ----------------------------------------------------------- random access

    def read_object(
        self,
        path: str,
        obj_out: Any = None,
        memory_budget_bytes: Optional[int] = None,
    ) -> Any:
        """Read a single object by manifest path ``"<rank>/<logical_path>"``
        without restoring anything else (reference snapshot.py:501-594)."""
        comm = get_communicator(self._comm)
        rank_str, _, logical_path = path.partition("/")
        if not rank_str.isdigit() or not logical_path:
            raise ValueError(
                f"Invalid manifest path {path!r} (expected '<rank>/<path>')"
            )
        with self._op_lock:
            return self._read_object_locked(
                path, rank_str, logical_path, obj_out, memory_budget_bytes, comm
            )

    def _read_object_locked(
        self, path, rank_str, logical_path, obj_out, memory_budget_bytes, comm
    ) -> Any:
        event_loop, storage = self._resources()
        metadata = self._get_metadata(storage, event_loop)
        local_manifest = get_manifest_for_rank(metadata, int(rank_str))
        if logical_path not in local_manifest:
            raise KeyError(f"{path!r} not found in snapshot manifest")
        entry = local_manifest[logical_path]
        if is_container_entry(entry):
            raise ValueError(
                f"{path!r} is a container; read its leaves individually"
            )
        read_reqs, fut = prepare_read(
            entry,
            obj_out,
            buffer_size_limit_bytes=memory_budget_bytes,
            logical_path=logical_path,
        )
        budget = memory_budget_bytes or get_process_memory_budget_bytes(comm)
        # Random access is the lazy-serving path the heatmap exists to
        # credit: scope just this object's reads so partial readers show
        # up with coverage << 1 instead of vanishing.
        with access.read_scope(
            self.path, default_source=self._access_default_source()
        ) as ledger:
            sync_execute_read_reqs(
                read_reqs, storage, budget, comm.rank, event_loop
            )
        if ledger is not None:
            self._publish_access_stats(ledger)
        return fut.obj

    def _access_default_source(self) -> str:
        """Ambient source tier for access-ledger records whose ReadIO
        carries no explicit stamp (tiering/CAS override per read)."""
        try:
            from .storage_plugin import storage_plugin_label

            _, storage = self._resources()
            return access.default_source_for_plugin(storage_plugin_label(storage))
        except Exception:
            return "local"

    def _publish_access_stats(self, ledger) -> None:
        """Fold a finished read scope's totals into this process's fleet
        reader record (the reader side of `tpusnap fleet`). The restore
        path stamps its telemetry meta separately, inside the wall.
        Best-effort: attribution never fails a read."""
        try:
            from . import fleet
            from .progress import _path_digest

            snapshot_bytes = 0
            if self._metadata is not None:
                snapshot_bytes = access.snapshot_stored_nbytes(self._metadata)
            fleet.note_reader_scope(
                _path_digest(self.path),
                snapshot_bytes,
                ledger.total_bytes,
                ledger.total_reads,
            )
        except Exception:
            logger.debug("fleet reader stats publish failed", exc_info=True)

    # ------------------------------------------------------------- integrity

    def verify(self):
        """Stream-verify every blob of this snapshot against the checksums
        recorded in its manifest (see :mod:`tpusnap.inspect`). Returns a
        :class:`tpusnap.inspect.ScrubReport`; ``report.clean`` is False on
        any corruption/truncation. Also exposed as
        ``python -m tpusnap verify <path>``."""
        from .inspect import verify_snapshot

        with self._op_lock:
            event_loop, storage = self._resources()
            return verify_snapshot(
                self.path,
                self._storage_options,
                metadata=self._metadata,
                resources=(event_loop, storage),
            )

    def materialize(self) -> Dict[str, int]:
        """Make an incremental snapshot self-contained by copying every
        base-referenced blob into it and rewriting the manifest (see
        :func:`tpusnap.inspect.materialize_snapshot`); afterwards the
        base snapshot(s) may be deleted. No-op on full snapshots."""
        from .inspect import materialize_snapshot

        with self._op_lock:
            event_loop, storage = self._resources()
            stats = materialize_snapshot(
                self.path,
                self._storage_options,
                resources=(event_loop, storage),
            )
            self._metadata = None  # manifest was rewritten on disk
        return stats

    # -------------------------------------------------------------- metadata

    @property
    def metadata(self) -> SnapshotMetadata:
        if self._metadata is None:
            with self._op_lock:
                event_loop, storage = self._resources()
                self._metadata = self._get_metadata(storage, event_loop)
        return self._metadata

    def get_manifest(self) -> Manifest:
        return dict(self.metadata.manifest)

    def _get_metadata(
        self, storage: StoragePlugin, event_loop: asyncio.AbstractEventLoop
    ) -> SnapshotMetadata:
        if self._metadata is not None:
            return self._metadata
        read_io = ReadIO(path=SNAPSHOT_METADATA_FNAME)
        try:
            storage.sync_read(read_io, event_loop)
        except Exception as e:
            raise RuntimeError(
                f"Failed to read snapshot metadata at "
                f"{self.path}/{SNAPSHOT_METADATA_FNAME} — not a snapshot, or "
                f"an aborted/incomplete one"
            ) from e
        from .manifest import MetadataError, decode_metadata

        try:
            self._metadata = decode_metadata(read_io.buf.getvalue())
        except MetadataError as e:
            raise RuntimeError(
                f"Corrupt snapshot metadata at "
                f"{self.path}/{SNAPSHOT_METADATA_FNAME}: {e} — run "
                f"`python -m tpusnap fsck {self.path}` to classify"
            ) from e
        except Exception as e:
            raise RuntimeError(
                f"Corrupt snapshot metadata at "
                f"{self.path}/{SNAPSHOT_METADATA_FNAME}"
            ) from e
        return self._metadata


# ---------------------------------------------------------------- internals


class _TakeAbortContext:
    """Failure-path bookkeeping for one take.

    Armed (multi-process) once G1 agrees the take_id: installs the
    :class:`TakeAbortMonitor` as the communicator's wait watcher, so
    every subsequent collective wait and commit barrier raises
    :class:`TakeAbortedError` within seconds of any rank's failure
    instead of burning the barrier timeout. On failure it publishes this
    rank's abort record, best-effort deletes the blobs this rank staged
    (so the path stays reusable and aborted takes leave no orphan
    storage), and drops this rank's late-checksum blob. Blob deletion is
    suppressed once the metadata commit may have started — orphan blobs
    are safe, dangling manifest references are not (the
    metadata-written-last ⟺ restorable invariant)."""

    def __init__(self, comm: Communicator) -> None:
        self.comm = comm
        self.monitor: Optional[TakeAbortMonitor] = None
        self.storage: Optional[StoragePlugin] = None
        self.event_loop: Optional[asyncio.AbstractEventLoop] = None
        self.write_paths: List[str] = []
        self.late_checksums: Optional["_LateChecksums"] = None
        self.tele_commit: Optional["_TelemetryCommit"] = None
        # Heartbeat/watchdog monitor (tpusnap.progress) — stopped with
        # a final "aborted" record on any failure path.
        self.progress = None
        # Rank-liveness layer (tpusnap.liveness): the lease this rank
        # publishes, the monitor every blocking wait consults, and —
        # when the failure policy is `degrade` — the context the
        # survivors complete a replicated-only take from.
        self.lease: Optional[LeasePublisher] = None
        self.liveness: Optional[LivenessMonitor] = None
        self.degrade: Optional["_DegradeContext"] = None
        self.commit_started = False
        # Set once the take's journal exists: an ABORTED take (as opposed
        # to a SIGKILLed one) cleans its blobs, so it also clears the
        # journal — leaving the path classifiably empty, not torn.
        self.journal_world_size: Optional[int] = None

    def arm(self, monitor: TakeAbortMonitor) -> None:
        self.monitor = monitor
        self._install_watcher()

    def arm_liveness(
        self, lease: LeasePublisher, liveness: LivenessMonitor
    ) -> None:
        """Installed once the heartbeat pump exists (strictly after
        ``arm``): the combined wait watcher now also judges lease
        staleness, so a blocked collective raises RankFailedError
        within ~2x TTL of a peer's death."""
        self.lease = lease
        self.liveness = liveness
        self._install_watcher()

    def _install_watcher(self) -> None:
        monitor, liveness = self.monitor, self.liveness
        if monitor is None:
            return
        if liveness is None:
            self.comm.set_wait_watcher(monitor.check)
            return

        def watcher() -> None:
            monitor.check()
            liveness.check()

        self.comm.set_wait_watcher(watcher)

    def barrier_watchers(self) -> List:
        """Watcher list for LinearBarrier-based waits (the async
        commit): peer-abort records AND lease expiry."""
        out = []
        if self.monitor is not None:
            out.append(self.monitor.check)
        if self.liveness is not None:
            out.append(self.liveness.check)
        return out

    def disarm(self) -> None:
        if self.monitor is not None:
            self.comm.clear_wait_watcher()

    def mark_commit_started(self) -> None:
        self.commit_started = True
        if self.monitor is not None:
            self.monitor.mark_commit_started()

    def on_failure(self, exc: BaseException) -> None:
        """Publish + clean up; never raises."""
        if self.progress is not None:
            try:
                self.progress.finish("aborted")
            except Exception:
                pass
        # SLO bookkeeping: release the dead take's telemetry record
        # (its counters would otherwise stay referenced for the process
        # lifetime) WITHOUT clearing the exposure — nothing committed,
        # so the planned bytes are still at risk.
        try:
            from . import slo as _slo

            _slo.tracker().note_take_aborted()
        except Exception:
            pass
        # The black box records the abort and force-flushes: an aborted
        # take's forensic breadcrumb survives even though its blobs and
        # journal are about to be cleaned.
        try:
            from . import flight as _flight_mod

            _flight_mod.record("abort", op=type(exc).__name__)
            _flight_mod.recorder().end_take("aborted")
        except Exception:
            pass
        if self.monitor is not None and not isinstance(exc, TakeAbortedError):
            self.monitor.publish(exc)
        # A RANK-FAILURE abort keeps everything: the survivors' completed
        # blobs are good bytes and the journal records are their salvage
        # evidence — deleting them would reduce the retake to byte zero,
        # and the dead rank cannot clean its own either way. The torn
        # state it leaves is exactly what fsck/timeline classify (naming
        # the dead rank) and what the retake's dual-hash salvage reuses.
        rank_failure = isinstance(exc, RankFailedError) or isinstance(
            getattr(exc, "__cause__", None), RankFailedError
        )
        keep_blobs = (
            self.commit_started
            or rank_failure
            or (
                self.monitor is not None
                and self.monitor.commit_may_have_started()
            )
        )
        if (
            not keep_blobs
            and self.storage is not None
            and self.event_loop is not None
        ):
            deletes_failed = False
            for path in self.write_paths:
                try:
                    self.storage.sync_delete(path, self.event_loop)
                except FileNotFoundError:
                    pass  # dedup/salvage-skipped or never-written path
                except Exception:
                    deletes_failed = True
            # Blobs gone: clear this rank's journal records (rank 0 also
            # the marker) so the path reads as empty, not torn. Records
            # go before the marker — a crash mid-cleanup stays torn. If
            # any of THIS rank's blob deletions failed, keep the marker
            # too: the leftovers must stay classifiable as torn (gc
            # --torn can finish the job), not become foreign debris.
            # Best-effort only across ranks — a PEER whose cleanup fails
            # after rank 0 cleared the marker still leaves foreign
            # files; that residual case needs a manual delete.
            if self.journal_world_size is not None:
                from .lifecycle import clear_journal, journal_rank_path

                try:
                    if self.comm.rank != 0:
                        self.storage.sync_delete(
                            journal_rank_path(self.comm.rank), self.event_loop
                        )
                    elif not deletes_failed:
                        clear_journal(
                            self.storage,
                            self.event_loop,
                            self.journal_world_size,
                        )
                except Exception:
                    pass
        if self.late_checksums is not None:
            try:
                self.late_checksums.discard()
            except Exception:
                pass
        if self.tele_commit is not None:
            try:
                self.tele_commit.discard()
            except Exception:
                pass
        if self.storage is not None and self.event_loop is not None:
            try:
                self.storage.sync_close(self.event_loop)
            except Exception:
                pass


def _validate_app_state(app_state: AppState) -> None:
    for key, stateful in app_state.items():
        if not (hasattr(stateful, "state_dict") and hasattr(stateful, "load_state_dict")):
            raise TypeError(
                f"app_state[{key!r}] ({type(stateful).__name__}) is not "
                "Stateful: it must define state_dict()/load_state_dict()"
            )


def _gather_keys(comm: Communicator, local_keys: List[str]) -> List[str]:
    if comm.world_size == 1:
        return sorted(local_keys)
    gathered = comm.all_gather_object(local_keys)
    merged: Set[str] = set()
    for keys in gathered:
        merged.update(keys)
    return sorted(merged)


def _take_impl(
    path: str,
    app_state: AppState,
    storage_options: Optional[Dict[str, Any]],
    comm: Communicator,
    replicated: List[str],
    event_loop: asyncio.AbstractEventLoop,
    is_async_snapshot: bool,
    per_key_barrier: bool = False,
    array_prepare_func: Optional[Any] = None,
    incremental_from: Optional[str] = None,
    abort_ctx: Optional["_TakeAbortContext"] = None,
    extras: Optional[Dict[str, Any]] = None,
    force_dedup_hashes: bool = False,
    force_clone_staging: bool = False,
    stream_capture: bool = False,
):
    """Core take flow. Exactly TWO all-gathers in the default
    multi-process path (the reference issues ~6 collectives,
    snapshot.py:752-853; the round-2 port issued 6 serial-KV gathers):

    - G1 (pre-staging): path + replicated globs + per-rank write-load
      estimates + hostnames ride one gather. Glob/path coalescing, the
      replicated-path intersection, the write-load partition plan (each
      rank runs the same deterministic argmin-greedy — no broadcast),
      and the local-world-size memory-budget divisor are all derived
      from it locally.
    - G2 (post-staging): the per-rank manifest gather, after stagers
      have recorded checksums into their entries.

    Plus the two commit barriers in ``take``. ``per_key_barrier=True``
    adds the reference's key gather + barrier-per-key for statefuls
    that run collectives inside ``state_dict()``.
    """
    _validate_app_state(app_state)
    rank = comm.rank
    multi = comm.world_size > 1
    # Contiguous phase spans (state_dict → plan → prepare → stage →
    # manifest_gather → metadata) tiling the take's timeline from t0;
    # the trace CLI's coverage figure is their sum over the take
    # wall-clock.
    mark = telemetry.phase_marker(from_start=True, first="state_dict")

    # Capture RNG state on entry; other statefuls' state_dict() calls may
    # consume RNG, and take() must be invariant (reference :332-374).
    rng_captured: Dict[str, Dict[str, Any]] = {
        k: v.state_dict() for k, v in app_state.items() if isinstance(v, RNGState)
    }

    if per_key_barrier and multi:
        # Safety mode: globally ordered state_dict() calls with a barrier
        # between keys (reference :352-368).
        keys = _gather_keys(comm, sorted(app_state.keys()))
    else:
        keys = sorted(app_state.keys())

    manifest: Manifest = {}
    flattened_all: Dict[str, Any] = {}
    for key in keys:
        if per_key_barrier and multi:
            comm.barrier()
        stateful = app_state.get(key)
        if stateful is None:
            continue
        state_dict = rng_captured.get(key) or stateful.state_dict()
        mft, flat = flatten(state_dict, prefix=key)
        manifest.update(mft)
        flattened_all.update(flat)

    # Undo any RNG perturbation caused by gathering state dicts.
    for key, captured in rng_captured.items():
        app_state[key].load_state_dict(captured)
    mark("state_dict", then="plan", keys=len(keys))

    # Local replicated candidates: glob-matched host-side values. A
    # fully-replicated multi-process jax.Array needs no glob — it routes
    # to the sharded preparer, whose replica-0 dedup stores one copy.
    globs = sorted(set(replicated))
    matched = {
        p
        for p in flattened_all
        if any(fnmatch.fnmatch(p, g) for g in globs)
    }

    assignment: Dict[str, int] = {}
    local_world_size: Optional[int] = None
    if multi:
        from .partitioner import assign_replicated_units, estimate_write_loads

        units, base_load, traced_map = estimate_write_loads(
            flattened_all, sorted(matched), array_prepare_func=array_prepare_func
        )
        from .knobs import get_node_name

        gathered = comm.all_gather_object(
            {
                "path": path,
                "globs": globs,
                "units": units,
                "base_load": base_load,
                "hostname": get_node_name(),
                # Scopes the late-checksum KV keys to this take; rank
                # 0's value wins (like the path) — riding G1 instead of
                # paying a broadcast.
                "take_id": uuid.uuid4().hex,
            }
        )
        take_id = gathered[0]["take_id"]
        # Path coalescing: rank 0's wins (reference :766-767).
        if gathered[0]["path"] != path:
            logger.warning(
                "Rank %d's snapshot path %r differs from rank 0's %r; "
                "using rank 0's",
                rank,
                path,
                gathered[0]["path"],
            )
        path = gathered[0]["path"]
        # Glob coalescing: only globs specified on every rank count
        # (reference :778-788).
        common_globs = set(gathered[0]["globs"])
        for g in gathered[1:]:
            common_globs &= set(g["globs"])
        dropped = set(globs) - common_globs
        if dropped:
            logger.warning(
                "Replicated globs %s were not specified on every rank; "
                "ignoring",
                sorted(dropped),
            )

        # A unit is partitionable when every rank listed it AND its path
        # matches a glob every rank specified.
        def unit_valid(uid: str) -> bool:
            p = uid.split("::", 1)[0]
            return any(fnmatch.fnmatch(p, g) for g in common_globs)

        assignment, replicated_paths = assign_replicated_units(
            [g["units"] for g in gathered],
            [g["base_load"] for g in gathered],
            unit_valid,
        )
        my_host = gathered[rank]["hostname"]
        local_world_size = sum(
            1 for g in gathered if g["hostname"] == my_host
        )
        traced_geometry = traced_map
        if abort_ctx is not None:
            # take_id is agreed: arm distributed abort propagation. From
            # here every collective wait in this take (the G2 gather's
            # barrier, the commit barriers/broadcasts) polls for peer
            # abort records and raises TakeAbortedError within seconds.
            abort_ctx.arm(
                TakeAbortMonitor(_get_kv_store(comm), take_id, rank)
            )
    else:
        # Single-process takes journal under their own id (no KV scoping
        # needed; _LateChecksums/_TelemetryCommit stay inactive at
        # world_size == 1 regardless).
        take_id = uuid.uuid4().hex
        replicated_paths = matched
        traced_geometry = {}
    # The G1 gather + write-load partition plan (single-process: just
    # the glob intersection — cheap, but keeping the phases contiguous
    # is what makes coverage meaningful).
    mark("plan", then="prepare")
    if mark.rec is not None:
        # Identity context for the summary consumers (export sinks,
        # cross-run history): take_id and the coalesced path are final
        # here. ``completed`` is set by the caller strictly after the
        # commit.
        mark.rec.meta.update(
            take_id=take_id,
            path=path,
            world_size=comm.world_size,
            incremental=incremental_from is not None,
        )

    storage = url_to_storage_plugin_in_event_loop(
        path, event_loop, storage_options
    )
    try:
        from .storage_plugin import storage_plugin_label

        # Which backend this take writes (innermost plugin class):
        # stamps the history event's `plugin` field — the tune
        # planner's cell key, and what keeps local-NVMe medians from
        # pricing cloud takes (restores have stamped it since PR 12).
        telemetry.current().meta["plugin"] = storage_plugin_label(storage)
    except Exception:
        pass
    # Auto-tuner reconcile (TPUSNAP_AUTOTUNE=1): install this cell's
    # plan BEFORE the staging/window/budget knob reads below; explicit
    # env vars win knob-by-knob, and the applied subset rides the
    # summary into the history event for attribution.
    from . import tune as _tune

    _tuned = _tune.maybe_apply(
        "take", storage=storage, world_size=comm.world_size
    )
    if _tuned:
        try:
            telemetry.current().meta["tuned"] = _tuned
        except Exception:
            pass
    # Crash-safe lifecycle (tpusnap.lifecycle): if the destination holds
    # a TORN take (journal present, no committed metadata), load its
    # completion records — staged blobs whose dual hash matches skip
    # their storage writes (salvage-resume). Then every rank wraps its
    # plugin in the journaling layer, and rank 0 writes the journal
    # marker BEFORE any blob write so a SIGKILLed take stays
    # distinguishable from a committed snapshot or foreign files.
    from .lifecycle import (
        JournalingStoragePlugin,
        TakeJournal,
        load_salvage_records,
        read_journal,
        write_journal,
    )

    from .knobs import is_journal_disabled

    journal_enabled = not is_journal_disabled()
    salvage_records = None
    # Covers every rank that may hold a journal record at this path: a
    # retake over a torn take with a LARGER world size must still clear
    # the torn ranks' record files at commit.
    journal_clear_ws = comm.world_size
    prior_journal = (
        read_journal(storage, event_loop) if journal_enabled else None
    )
    if prior_journal is not None:
        journal_clear_ws = max(journal_clear_ws, prior_journal.world_size)
        try:
            files = storage.sync_list_with_sizes(event_loop)
        except Exception:
            files = None
        # Salvage requires a listing (load_salvage_records cross-checks
        # every record against the blobs actually present — load-bearing
        # for correctness); it also gives the metadata-existence probe
        # for free (a committed snapshot with a stale journal must NOT
        # trigger salvage).
        if files is not None and SNAPSHOT_METADATA_FNAME not in files:
            salvage_records = load_salvage_records(
                storage, event_loop, prior_journal.world_size, files=files
            )
            if salvage_records:
                logger.info(
                    "Torn take %s found at %r: %d completed blob record(s) "
                    "loaded for salvage-resume",
                    prior_journal.take_id[:8],
                    path,
                    len(salvage_records),
                )
    # The CAS layer (if composed) was built before the take knew its
    # rank: per-rank ref records need it (rank 0's file must not be
    # clobbered by rank 3's flush).
    from .cas import find_cas_plugin

    cas_layer = find_cas_plugin(storage)
    if cas_layer is not None:
        cas_layer.rank = rank
    storage = JournalingStoragePlugin(storage, rank, salvage_records)
    storage.clear_world_size = journal_clear_ws
    if journal_enabled:
        if rank == 0:
            import time as _time

            write_journal(
                storage,
                event_loop,
                TakeJournal(
                    take_id=take_id,
                    world_size=comm.world_size,
                    started_at=_time.time(),
                    incremental_from=incremental_from,
                    version=__version__,
                    # Delta-chain membership rides the journal so a
                    # SIGKILLed micro-commit stays explainable as
                    # "seq N over member X", not an anonymous torn take.
                    stream=(extras or {}).get("delta"),
                ),
            )
        # EVERY rank eagerly creates its record file before any of its
        # blob writes: the journal-before-blobs invariant would
        # otherwise be rank-0-only — a gang-SIGKILL while a fast peer
        # wrote blobs before rank 0's marker landed would leave debris
        # fsck can only call foreign. Any journal-family file counts as
        # take evidence, so the unclassifiable window shrinks to this
        # one tiny write per rank. The write carries the SEEDED salvage
        # records (not an empty map), so a salvage-retake that itself
        # crashes early still leaves the torn take's evidence for the
        # third attempt.
        try:
            storage.sync_seed_record_file(event_loop)
        except Exception:
            logger.warning(
                "Failed to create journal record file (non-fatal)",
                exc_info=True,
            )
    if abort_ctx is not None:
        abort_ctx.storage = storage
        if journal_enabled:
            abort_ctx.journal_world_size = journal_clear_ws

    # Live observability (tpusnap.progress): heartbeat pump + stall
    # watchdog for the rest of this take. Telemetry-off takes skip the
    # subsystem entirely; everything it does is best-effort.
    progress_monitor = None
    if mark.rec is not None and mark.rec.enabled:
        try:
            from .progress import start_take_monitor

            progress_monitor = start_take_monitor(
                mark.rec, comm, take_id, path
            )
            if abort_ctx is not None:
                abort_ctx.progress = progress_monitor
        except Exception:
            logger.warning(
                "Failed to start progress monitor (non-fatal)", exc_info=True
            )
    # Black-box flight recorder (tpusnap.flight): arm this take's
    # crash-surviving flush destinations and piggyback the periodic
    # flush on the heartbeat pump — from here, a SIGKILL loses at most
    # one flush interval of events (`python -m tpusnap timeline`).
    try:
        from . import flight as _flight_mod
        from .progress import local_root_of

        _frec = _flight_mod.recorder()
        _frec.configure_take(
            rank, take_id, comm.world_size, path, local_root_of(path)
        )
        if progress_monitor is not None and _frec.enabled:
            progress_monitor.add_tick_hook(_flight_mod.make_tick_hook(_frec))
    except Exception:
        logger.warning(
            "Failed to configure flight recorder (non-fatal)", exc_info=True
        )
    # Rank-liveness leases (tpusnap.liveness): this rank's lease rides
    # the heartbeat pump (no new thread) and the monitor joins every
    # blocking wait's watcher, so a SIGKILLed peer fails the take with
    # RankFailedError within ~2x TPUSNAP_LIVENESS_TTL_S instead of
    # parking until the barrier timeout. Requires the pump (telemetry
    # on — SPMD-identical on every rank) and a coordination KV.
    if (
        multi
        and abort_ctx is not None
        and progress_monitor is not None
        and progress_monitor.kv is not None
    ):
        from .knobs import get_liveness_ttl_s

        ttl = get_liveness_ttl_s()
        if ttl > 0:
            try:
                lease = LeasePublisher(progress_monitor.kv, take_id, rank)
                lease.publish()  # alive NOW, not one pump tick later
                liveness_monitor = LivenessMonitor(
                    progress_monitor.kv,
                    take_id,
                    rank,
                    comm.world_size,
                    ttl_s=ttl,
                )
                progress_monitor.add_tick_hook(lease.make_tick_hook())
                progress_monitor.set_liveness_probe(
                    liveness_monitor.dead_ranks
                )
                progress_monitor.set_left_probe(
                    liveness_monitor.left_ranks
                )
                abort_ctx.arm_liveness(lease, liveness_monitor)
            except Exception:
                logger.warning(
                    "Failed to arm rank-liveness leases (non-fatal)",
                    exc_info=True,
                )

    # Checkpoint-SLO tracker (tpusnap.slo): the exposure gauges (RPO,
    # data-at-risk, estimated RTO) publish at the heartbeat cadence on
    # the same pump thread, and the slo sub-dict rides every heartbeat
    # record (what `watch`'s at-risk column and rank 0's fleet fold
    # read). Best-effort like everything observability.
    if progress_monitor is not None:
        try:
            from . import slo as _slo

            _slo.tracker().refresh_rto()
            _slo.attach_to_take(
                progress_monitor, take_id, rank, comm.world_size
            )
        except Exception:
            logger.warning(
                "Failed to attach SLO tracker (non-fatal)", exc_info=True
            )

    # Fleet status mirror (tpusnap.fleet): when TPUSNAP_FLEET_DIR is
    # set, rank 0 republishes this job's compact status record into the
    # shared fleet directory on the same tick-hook pump — what
    # `tpusnap fleet` aggregates across jobs. No-op otherwise.
    if progress_monitor is not None:
        try:
            from . import fleet as _fleet

            _fleet.attach_to_take(progress_monitor)
        except Exception:
            logger.warning(
                "Failed to attach fleet publisher (non-fatal)", exc_info=True
            )

    # Incremental snapshot: this rank's view of the base snapshot's
    # manifest, blob locations rewritten relative to the NEW root.
    prev_entries: Manifest = {}
    if incremental_from is not None:
        from .knobs import is_checksum_disabled

        if is_checksum_disabled():
            # Dedup compares stage-time checksums; without them every
            # blob would silently rewrite in full — refuse instead.
            raise ValueError(
                "incremental_from requires checksums; unset "
                "TPUSNAP_DISABLE_CHECKSUM to take an incremental snapshot"
            )
        prev_entries, base_root_candidates = _load_prev_entries(
            incremental_from, storage_options, rank, path, event_loop
        )
    else:
        base_root_candidates = []

    entries: Manifest = dict(manifest)
    write_reqs = []
    replicated_entry_paths: List[str] = []
    from .knobs import is_dedup_hash_recording_forced

    record_dedup_hashes = (
        incremental_from is not None
        or force_dedup_hashes
        or is_dedup_hash_recording_forced()
    )
    for logical_path, leaf in flattened_all.items():
        is_repl = logical_path in replicated_paths
        entry, reqs = prepare_write(
            obj=leaf,
            logical_path=logical_path,
            rank=rank,
            replicated=is_repl,
            is_async_snapshot=is_async_snapshot,
            array_prepare_func=(
                functools.partial(array_prepare_func, logical_path)
                if array_prepare_func is not None
                else None
            ),
            array_prepare_traced=traced_geometry.get(logical_path),
            prev_entry=prev_entries.get(logical_path),
            record_dedup_hashes=record_dedup_hashes,
            # Multi-process replicated entries keep blob-grain geometry:
            # the write-load estimator's unit ids (computed on every
            # rank without prev-entry knowledge) must match what was
            # prepared.
            allow_tile_dedup=not (multi and is_repl),
        )
        entries[logical_path] = entry
        if is_repl and is_replicated(entry):
            replicated_entry_paths.append(logical_path)
        write_reqs.extend(reqs)

    # Keep only the replicated write requests the plan assigned to this
    # rank (plan computed identically on every rank from G1 — the
    # reference's rank-0-compute + broadcast is one more collective).
    dropped_replicated: Dict[str, List] = {}
    if multi and replicated_entry_paths:
        from .partitioner import filter_assigned_write_reqs

        write_reqs, dropped_replicated = filter_assigned_write_reqs(
            entries, write_reqs, replicated_entry_paths, assignment, rank
        )

    # Slab-batch small writes.
    from .batcher import batch_write_requests

    entries_list = list(entries.values())
    entries_list, write_reqs = batch_write_requests(entries_list, write_reqs)
    entries = dict(zip(entries.keys(), entries_list))

    # Fused tile compression (tpusnap.compress): ONE measured
    # compress-or-bypass decision per take — what the codec takes off
    # the pipe, by its ratio and rate on a sample of this take's own
    # host bytes, against the probe-reported pipe ceiling — armed on
    # the eligible stagers (standalone dense blobs after batching; slab
    # members and shards bypass by construction). Made before
    # scheduling, which reads the stagers' staging cost. Never fails a
    # take.
    from . import compress as _compress

    _compress.apply_take_policy(write_reqs, storage, event_loop, rec=mark.rec)
    if force_clone_staging:
        # Per-TAKE clone-staging override (delta micro-commits:
        # free-running captures cannot rendezvous with the training
        # thread, so COW's write-time mutation check would fail every
        # commit). Armed on the stagers like the compress policy above
        # — scoped to THIS take's requests, never a process-global env
        # flip that would race concurrent takes on other threads.
        # Batched slabs hold their members as (offset, nbytes, stager)
        # tuples; the member stagers are the ones that consult COW.
        def _arm_clone(st):
            if hasattr(st, "force_clone"):
                st.force_clone = True
            for _m in getattr(st, "members", None) or []:
                _arm_clone(_m[2] if isinstance(_m, tuple) else _m)

        for _wr in write_reqs:
            _arm_clone(_wr.buffer_stager)
    if abort_ctx is not None:
        # The final set of blob paths this rank may write — an aborting
        # take best-effort deletes them so the path stays reusable
        # (dedup-skipped paths are never written; deleting them is a
        # harmless no-op failure).
        abort_ctx.write_paths = [wr.path for wr in write_reqs]
    planned_payload = sum(
        wr.buffer_stager.get_planned_bytes() for wr in write_reqs
    )
    if progress_monitor is not None:
        # Denominator of the heartbeat's byte progress — PAYLOAD bytes,
        # not staging cost (async array clones charge 2x cost; dividing
        # written/staged bytes by that capped the percentages at ~50).
        # Dedup/salvage skips make written < planned, so the committed
        # record forces 100% (the mid-flight figure is best-effort by
        # design).
        progress_monitor.set_bytes_planned(planned_payload)
    # Data-at-risk floor (tpusnap.slo): everything this take stages is
    # at risk until its commit clears it; incremental takes refine the
    # figure live from the dual-hash skip counters. Recorded even with
    # telemetry off (the tracker is bookkeeping, not spans).
    try:
        from . import slo as _slo

        _rec = mark.rec
        # Identity must not depend on the telemetry knob: attach (the
        # tick-hook wiring) is skipped when the pump is off, but the
        # sidecar/commit bookkeeping still runs per rank.
        _slo.tracker().configure(rank, comm.world_size)
        _slo.tracker().note_planned(
            planned_payload,
            incremental=incremental_from is not None,
            live_counters=(
                (lambda: _rec.live_snapshot()["counters"])
                if _rec is not None
                else None
            ),
            # The capture anchor: this take's commit makes THIS
            # instant's state durable — not the (possibly minutes
            # later) commit instant.
            take_id=take_id,
        )
    except Exception:
        logger.debug("slo note_planned failed", exc_info=True)

    # Non-incremental takes hash on the WRITE path instead of the
    # staging window (see ArrayBufferStager.defer_checksums) — the hash
    # pass moves off the window async_take blocks training on. With
    # world_size == 1 the gathered manifest holds the SAME entry
    # objects the stagers annotate, so late values land in the commit
    # directly; multi-process manifests gather by VALUE at
    # staging-complete, so the late values ride the commit barrier's KV
    # store instead (_LateChecksums). Applied after batching: slab
    # members hash inside their slab's staging (the member write reqs
    # no longer exist to carry a late hash). Incremental takes need
    # hashes at stage time for dedup and never defer.
    late_checksums: Optional[_LateChecksums] = _NO_LATE_CHECKSUMS
    if incremental_from is None:
        from .io_preparers.array import ArrayBufferStager

        deferred = []
        for wr in write_reqs:
            if isinstance(wr.buffer_stager, ArrayBufferStager):
                wr.buffer_stager.defer_checksums = True
                deferred.append(wr.buffer_stager)
        if multi:
            late_checksums = _LateChecksums(comm, take_id, deferred)
            if abort_ctx is not None:
                abort_ctx.late_checksums = late_checksums

    memory_budget = get_process_memory_budget_bytes(
        comm, local_world_size=local_world_size
    )
    mark("prepare", then="stage", write_reqs=len(write_reqs))
    # Async-take scheduling mode. PIPELINED (the default async path):
    # the blocked window stages only a TPUSNAP_ASYNC_STAGE_WINDOW_BYTES
    # window of write requests before control returns, and only of
    # those whose bytes the caller could write in place afterwards
    # (scheduler._WriteScheduler: an accelerator-resident leaf never
    # counts); the rest is staged on the background drain, interleaved
    # with its storage I/O — blocked time and clone RSS are O(window),
    # not O(state). Incremental takes cannot pipeline: their dedup
    # decisions mutate entry locations at stage time and must be final
    # before the manifest gather below, so they keep the strict
    # stage-everything-first mode (their blocked window is inherently
    # the hash pass). A window of 0 also restores strict semantics.
    from .knobs import get_async_stage_window_bytes

    pipelined = (
        is_async_snapshot
        and incremental_from is None
        and get_async_stage_window_bytes() is not None
    )
    stage_eagerly = None
    if pipelined and multi:
        # Multi-process manifests gather BY VALUE right after this call
        # returns: stagers that annotate entries at stage time (slabs,
        # objects — everything that does not defer its checksums to the
        # write path) must stage inside the blocked window or their
        # values would miss the gathered manifest. Deferring array
        # stagers transport theirs through _LateChecksums instead.
        stage_eagerly = lambda wr: not getattr(  # noqa: E731
            wr.buffer_stager, "defer_checksums", False
        )
    pending_io_work = sync_execute_write_reqs(
        write_reqs,
        storage,
        memory_budget,
        rank,
        event_loop,
        # Non-pipelined async takes: training is blocked until staging
        # completes, so writes wait their turn (they drain in the
        # background via PendingIOWork) instead of stealing CPU from
        # the staging pass — see scheduler._WriteScheduler.
        prioritize_staging=is_async_snapshot and not pipelined,
        pipelined_staging=pipelined,
        stage_eagerly=stage_eagerly,
    )
    # The manifest is gathered once sync_execute returns (storage I/O —
    # and, for pipelined async takes, residual staging windows — may
    # still be in flight): stagers whose entry annotations must land in
    # the gathered manifest have staged by now (everything, for sync and
    # incremental takes; the eager set above for pipelined multi-process
    # takes — single-process manifests share the entry OBJECTS, whose
    # late annotations land before the commit encodes them). The
    # reference gathers before scheduling (snapshot.py:842-853) only
    # because its entries are final at prepare time.
    # The "stage" phase is the window async_take blocks training on
    # (first-window-staged for pipelined takes, staging-complete
    # otherwise); the scheduler's "stage_blocked"/"stage_window" op
    # spans are the interior measurements.
    mark("stage", then="manifest_gather", write_reqs=len(write_reqs))
    from .knobs import get_rank_failure_policy

    if (
        multi
        and abort_ctx is not None
        and abort_ctx.liveness is not None
        and (
            (incremental_from is None and not is_async_snapshot)
            or stream_capture
        )
        and get_rank_failure_policy() == "degrade"
    ):
        # Everything a degraded commit needs is final here — armed
        # BEFORE the manifest gather, the first all-ranks wait a dead
        # peer can strand: from this point a RankFailedError in any
        # collective or commit wait can hand the survivors a complete
        # recovery context. Armed ONLY under the degrade policy (the
        # retained dropped reqs pin the caller's replicated buffers
        # across the commit window — a cost abort-mode users must not
        # pay) and ONLY for sync takes: an async caller may mutate
        # host-aliasing state the moment control returns, so adoption's
        # re-staging could capture post-return bytes for the adopted
        # values while the rest of the snapshot holds the capture-time
        # state — async rank failures abort fast instead (still
        # seconds, torn and salvageable). Incremental takes never
        # degrade either (their dedup decisions reference per-rank base
        # views the dead rank's evidence is part of).
        #
        # STREAM CAPTURES (`stream_capture=True`, delta-stream epoch
        # micro-commits) are the deliberate exception to both
        # exclusions, because the stream pins both hazards shut:
        # `_force_clone_staging` freezes every byte into take-owned
        # clones before control returns (adoption re-stages CLONED
        # capture-time bytes, never post-return caller state), and the
        # stream's epoch protocol hands every member the same parent
        # member as the dedup base, with replicated state SPMD-
        # identical across members — so a survivor's dedup view of a
        # replicated entry is byte-for-byte the dead rank's. A sharded
        # leaf still refuses inside the degraded commit itself
        # (_degrade_eligible), aborting to a torn, salvageable epoch.
        abort_ctx.degrade = _DegradeContext(
            comm=comm,
            take_id=take_id,
            storage=storage,
            event_loop=event_loop,
            entries=entries,
            dropped_replicated=dropped_replicated,
            assignment=assignment,
            memory_budget=memory_budget,
            extras=dict(extras) if extras else None,
            pending_io_work=pending_io_work,
        )
    global_manifest = _gather_manifest(entries, comm)
    mark("manifest_gather", then="metadata")
    import time

    metadata = SnapshotMetadata(
        version=__version__,
        world_size=comm.world_size,
        manifest=global_manifest,
        created_at=time.time(),
        # Record which base roots the external references point into:
        # retention/info/materialize then never parse roots out of
        # location strings (ambiguous when a base path contains a
        # numeric directory). Computed from the gathered manifest, so
        # identical on the rank that commits.
        base_roots=_referenced_base_roots(
            global_manifest, base_root_candidates
        )
        or None,
        # Caller-provided sidecar data (e.g. a delta stream's chain
        # fields) — merged under, never over, the commit-time additions
        # (the telemetry rollup lands on top of this dict).
        extras=dict(extras) if extras else None,
    )
    mark("metadata")
    tele_commit = _TelemetryCommit(
        mark.rec, comm, take_id, progress=progress_monitor
    )
    if abort_ctx is not None:
        abort_ctx.tele_commit = tele_commit
    return pending_io_work, metadata, path, storage, late_checksums, tele_commit


def _referenced_base_roots(
    manifest: Manifest, candidates: List[str]
) -> List[str]:
    """The subset of candidate base roots actually referenced by the
    manifest's external (``../``) blob locations — matched with the
    SAME longest-prefix rule readers use (``base_root_of_location``),
    so what the writer records is byte-identical to what a reader
    resolves."""
    if not candidates:
        return []
    from .inspect import base_root_of_location

    roots = set()
    for entry in manifest.values():
        for t in _prev_entry_tensors(entry):
            loc = t.location
            if not loc.startswith("../"):
                continue
            matched = base_root_of_location(loc, known_roots=candidates)
            if matched in candidates:
                roots.add(matched)
    return sorted(roots)


def _relative_ref_prefix(base_path: str, new_path: str) -> str:
    """Relative reference from the NEW snapshot root to the BASE
    snapshot root (``"../step_1000"`` for siblings). Cross-snapshot blob
    references are stored relative so a snapshot tree moves/renames as a
    unit; both snapshots must live on the same scheme and bucket/host."""
    import os
    import posixpath
    from urllib.parse import urlsplit

    # Write-back tier URLs are not urlsplit-parseable (the scheme embeds
    # a path); do the relative math on the LOCAL mirror dirs — the
    # mirror layout guarantees the same relative relationship holds in
    # the remote tier, so one recorded reference serves both.
    from .tiering import parse_tier_url

    try:
        for is_base, url in ((True, base_path), (False, new_path)):
            spec = parse_tier_url(url)
            if spec is not None:
                if is_base:
                    base_path = spec.local_dir
                else:
                    new_path = spec.local_dir
    except ValueError:
        pass  # malformed tier URL: fall through to the plain-path error

    a, b = urlsplit(base_path), urlsplit(new_path)
    if a.scheme != b.scheme or a.netloc != b.netloc:
        raise ValueError(
            f"incremental_from {base_path!r} must share the scheme and "
            f"bucket/host of the snapshot path {new_path!r}"
        )
    if a.scheme in ("", "file"):
        pa, pb = os.path.abspath(a.path or base_path), os.path.abspath(
            b.path or new_path
        )
    else:
        pa, pb = a.path, b.path
    rel = posixpath.relpath(pa, pb)
    if rel == ".":
        raise ValueError(
            "incremental_from must name a different snapshot than the one "
            "being taken"
        )
    return rel


def _rewrite_entry_locations(entry: Entry, rel_prefix: str) -> Entry:
    """Deep copy of ``entry`` with every blob location re-expressed
    relative to the new snapshot root (collapsing chained references:
    a base that itself references an older base resolves to the older
    one directly, so incremental chains do not deepen lookups)."""
    import copy
    import posixpath

    from .manifest import ChunkedTensorEntry, ObjectEntry, ShardedEntry, TensorEntry

    e = copy.deepcopy(entry)

    def fix(t):
        t.location = posixpath.normpath(posixpath.join(rel_prefix, t.location))

    if isinstance(e, (TensorEntry, ObjectEntry)):
        fix(e)
    elif isinstance(e, ChunkedTensorEntry):
        for c in e.chunks:
            fix(c.tensor)
    elif isinstance(e, ShardedEntry):
        for s in e.shards:
            fix(s.tensor)
    return e


def _load_prev_entries(
    incremental_from: str,
    storage_options: Optional[Dict[str, Any]],
    rank: int,
    new_path: str,
    event_loop: asyncio.AbstractEventLoop,
):
    """This rank's manifest view of the base snapshot (replicated
    re-expansion + sharded merge, like restore uses), with every blob
    location rewritten relative to the new snapshot root — ready to hand
    to ``prepare_write`` as dedup candidates. Returns
    ``(entries, base_root_candidates)``: the candidates are every base
    root a rewritten location can point into — the base itself plus the
    base's own recorded roots (chained references collapse through
    them), re-expressed relative to the new snapshot."""
    import posixpath

    rel_prefix = _relative_ref_prefix(incremental_from, new_path)
    storage = url_to_storage_plugin_in_event_loop(
        incremental_from, event_loop, storage_options
    )
    try:
        from .manifest import decode_metadata

        read_io = ReadIO(path=SNAPSHOT_METADATA_FNAME)
        try:
            storage.sync_read(read_io, event_loop)
            prev_md = decode_metadata(read_io.buf.getvalue())
        except Exception as e:
            raise RuntimeError(
                f"incremental_from={incremental_from!r} is not a readable "
                "snapshot (missing or corrupt .snapshot_metadata)"
            ) from e
    finally:
        storage.sync_close(event_loop)
    view = get_manifest_for_rank(prev_md, rank)

    # Dedup compares stage-time checksums against the base's. A base
    # taken with checksums disabled (or by a build with a different
    # checksum algorithm) can never match — every blob would silently
    # rewrite in full, the exact outcome incremental_from exists to
    # avoid. Refuse up front while the user can still fix it.
    from . import _native
    from .inspect import entry_nbytes

    algo_prefix = _native.checksum_algorithm() + ":"
    blob_entries = [e for e in view.values() if entry_nbytes(e) > 0]
    usable = any(
        (t.checksum or "").startswith(algo_prefix)
        for e in blob_entries
        for t in _prev_entry_tensors(e)
    )
    if blob_entries and not usable:
        raise ValueError(
            f"incremental_from={incremental_from!r} carries no "
            f"{algo_prefix[:-1]} checksums (taken with checksums disabled "
            "or by a different build?) — dedup is impossible, every blob "
            "would silently rewrite in full"
        )
    candidates = [rel_prefix] + [
        posixpath.normpath(posixpath.join(rel_prefix, r))
        for r in (prev_md.base_roots or [])
    ]
    return (
        {p: _rewrite_entry_locations(e, rel_prefix) for p, e in view.items()},
        candidates,
    )


def _prev_entry_tensors(entry: Entry):
    from .manifest import (
        ChunkedTensorEntry,
        ObjectEntry,
        ShardedEntry,
        TensorEntry,
    )

    if isinstance(entry, (TensorEntry, ObjectEntry)):
        yield entry
    elif isinstance(entry, ChunkedTensorEntry):
        for c in entry.chunks:
            yield c.tensor
    elif isinstance(entry, ShardedEntry):
        for s in entry.shards:
            yield s.tensor


def _gather_manifest(entries: Manifest, comm: Communicator) -> Manifest:
    """All-gather per-rank manifests; key by ``rank/logical_path``;
    consolidate replicated entries onto rank 0, preferring the writer's
    (possibly slab-batched) entry version (reference :842-853,
    partitioner.py:262-303)."""
    from .partitioner import consolidate_replicated_entries

    if comm.world_size == 1:
        per_rank = [entries]
    else:
        per_rank = comm.all_gather_object(entries)
    return consolidate_replicated_entries(per_rank)


class _LateChecksums:
    """Transports write-path-deferred checksums into every rank's
    manifest before the metadata commit (VERDICT r4: deferral was
    restricted to world_size == 1 because multi-process manifests
    gather by VALUE at staging-complete, before the write path has
    hashed anything — so multi-process takes paid the whole hash pass
    inside the blocked window).

    Pure KV traffic riding the commit protocol's existing
    synchronization — zero extra collectives, usable from the async
    commit's background thread:

    - after a rank's writes drain (all its late checksums recorded in
      its own entry objects), ``publish`` puts one blob of
      {location: field tuple} under a take-scoped key;
    - after the commit barrier's arrive phase (every rank arrived ⟹
      every rank published), EVERY rank ``apply``s: ONE ``try_get_dir``
      RPC collects every rank's blob (not world_size serial gets — the
      O(N²) pattern ``all_gather_object`` was engineered away from)
      and patches that rank's stale by-value manifest copy by blob
      location. Rank 0's patch is load-bearing (it writes the file);
      non-leader patches let the take hand every rank a handle with
      CACHED metadata instead of world_size−1 metadata GETs against
      cloud storage on first access (ADVICE r5 #4) — a non-leader
      patch failure just falls back to the lazy file read;
    - ``cleanup`` (rank 0 only, strictly after the SECOND commit
      barrier — every rank passed it ⟹ every rank has read the blobs)
      DELETES the key prefix, so the coordination service does not
      accumulate one blob per rank per take for the job's lifetime.

    ``take_id`` is agreed via the take's existing G1 gather (rank 0's
    value), not a new broadcast. Every rank publishes — possibly an
    empty dict — whenever deferral is enabled, so rank 0 can detect a
    missing blob as an error rather than a slow rank."""

    def __init__(self, comm: Communicator, take_id: str, stagers) -> None:
        self.comm = comm
        self.take_id = take_id
        self.stagers = stagers

    @property
    def active(self) -> bool:
        return self.comm.world_size > 1

    def _key(self, rank: int) -> str:
        return f"tpusnap_late_cs/{self.take_id}/{rank}"

    def publish(self) -> None:
        if not self.active:
            return
        import pickle

        fields = {}
        for st in self.stagers:
            e = st.entry
            if e is None or e.checksum is None:
                continue
            fields[e.location] = (
                e.checksum,
                e.tile_rows,
                e.tile_checksums,
                e.dedup_hash,
                # Without the per-tile hashes the committed base loses
                # tile-grain dedup for the NEXT increment (the 64-bit
                # evidence rule would force a whole-blob rewrite).
                e.tile_dedup_hashes,
                # Compressed-blob layout fields: a compressed stager
                # annotates these at stage time (fused with the codec
                # pass) but pipelines like any deferring stager, so
                # they ride the same KV transport into every rank's
                # by-value manifest copy.
                e.codec,
                e.uncompressed_nbytes,
                e.comp_tile_sizes,
            )
        _get_kv_store(self.comm).set(
            self._key(self.comm.rank), pickle.dumps(fields)
        )

    def _prefix(self) -> str:
        return f"tpusnap_late_cs/{self.take_id}/"

    def discard(self) -> None:
        """Abort path: best-effort removal of this rank's published blob
        — the commit that would have consumed and deleted it will never
        run, and the coordination service must not accumulate one blob
        per rank per aborted take."""
        if not self.active:
            return
        try:
            _get_kv_store(self.comm).delete_prefix(self._key(self.comm.rank))
        except Exception:
            pass

    def apply(self, manifest: Manifest) -> None:
        """Patch this rank's manifest copy from the published blobs.
        Callers hold proof every rank published (all ranks arrived at
        the commit barrier). Read-only on the KV store — see
        ``cleanup`` for the deletion."""
        if not self.active:
            return
        import pickle

        from .manifest import ChunkedTensorEntry, ShardedEntry, TensorEntry

        by_loc: Dict[str, TensorEntry] = {}
        for entry in manifest.values():
            if isinstance(entry, TensorEntry):
                tes = [entry]
            elif isinstance(entry, ChunkedTensorEntry):
                tes = [c.tensor for c in entry.chunks]
            elif isinstance(entry, ShardedEntry):
                tes = [s.tensor for s in entry.shards]
            else:
                continue
            for te in tes:
                by_loc[te.location] = te
        store = _get_kv_store(self.comm)
        blobs = store.try_get_dir(self._prefix())
        if blobs is None or len(blobs) < self.comm.world_size:
            # Backend without dir-get (or a torn listing): per-key
            # fallback.
            blobs = {
                self._key(r): store.get(self._key(r), timeout_sec=120.0)
                for r in range(self.comm.world_size)
            }
        for raw in blobs.values():
            for loc, fields in pickle.loads(raw).items():
                cs, tr, tcs, dh, tdh = fields[:5]
                codec, unb, cts = (
                    fields[5:8] if len(fields) >= 8 else (None, None, None)
                )
                te = by_loc.get(loc)
                if te is None:
                    continue  # e.g. an elastic reader's partial view
                if te.checksum is None:
                    te.checksum = cs
                    te.tile_rows = tr
                    te.tile_checksums = tcs
                if te.dedup_hash is None:
                    te.dedup_hash = dh
                if te.tile_dedup_hashes is None:
                    te.tile_dedup_hashes = tdh
                if te.codec is None and codec is not None:
                    te.codec = codec
                    te.uncompressed_nbytes = unb
                    te.comp_tile_sizes = cts

    def cleanup(self) -> None:
        """Leader-only, strictly after the final commit barrier (every
        rank passed it ⟹ every rank has applied): delete the take-scoped
        keys so the coordination service does not grow per take."""
        if not self.active:
            return
        _get_kv_store(self.comm).delete_prefix(self._prefix())


_NO_LATE_CHECKSUMS = None  # single-process takes thread None through


class _TelemetryCommit:
    """Transport for per-take telemetry (:mod:`tpusnap.telemetry`),
    riding the commit protocol exactly like :class:`_LateChecksums`:

    - ``persist`` (every rank, writes drained, BEFORE the commit
      barrier): freeze the recorder, write this rank's Chrome trace to
      ``.tpusnap/telemetry/rank_<k>.json`` through the take's own
      storage plugin, and publish the compact summary under a
      take-scoped KV key. Persisting before the barrier preserves the
      metadata-written-last invariant: an abort can orphan a trace
      file (registered for the abort path's blob cleanup), but a
      committed snapshot never references state that predates its
      traces.
    - ``apply`` (rank 0, after the barrier's arrive ⟹ every rank
      published): ONE ``try_get_dir`` collects the summaries, the
      cross-rank rollup lands in ``metadata.extras["telemetry"]``, and
      the KV prefix is deleted.

    Everything is best-effort: telemetry failures log and never fail a
    take."""

    def __init__(
        self,
        tele: Optional[telemetry.TakeTelemetry],
        comm: Communicator,
        take_id: Optional[str],
        progress=None,
    ) -> None:
        self.tele = tele
        self.comm = comm
        self.take_id = take_id
        self.progress = progress
        self._summary: Optional[Dict[str, Any]] = None

    def finish_progress(self, state: str = "committed") -> None:
        """Publish the final heartbeat (100% at commit) and stop the
        pump; idempotent and best-effort like everything here."""
        if self.progress is not None:
            try:
                self.progress.finish(state)
            except Exception:
                pass

    def stop_progress(self) -> None:
        if self.progress is not None:
            try:
                self.progress.stop()
            except Exception:
                pass

    def _prefix(self) -> str:
        return f"tpusnap_tele/{self.take_id}/"

    def _key(self, rank: int) -> str:
        return f"{self._prefix()}{rank}"

    def persist(
        self,
        storage: StoragePlugin,
        event_loop: asyncio.AbstractEventLoop,
        abort_ctx: Optional["_TakeAbortContext"] = None,
        prep_start: Optional[float] = None,
    ) -> None:
        if self.tele is None:
            return
        try:
            self.tele.finalize()
            if prep_start is not None:
                # Tail phase (durable dirent flush + late-checksum
                # publish, between the I/O drain and this freeze) so the
                # phases tile the whole persisted wall-clock.
                self.tele.record_span(
                    "commit_prep",
                    prep_start,
                    max(self.tele.take_wall_s - prep_start, 0.0),
                    phase=True,
                )
            self._summary = self.tele.summary()
        except Exception:
            logger.warning("Telemetry summary failed (non-fatal)", exc_info=True)
            return
        if self.tele.enabled:
            from .telemetry import telemetry_rank_path

            trace_path = telemetry_rank_path(self.tele.rank)
            if abort_ctx is not None:
                # An aborting take deletes its staged blobs so the path
                # stays reusable; the trace file is cleaned up with them.
                abort_ctx.write_paths.append(trace_path)
            try:
                storage.sync_write(
                    WriteIO(path=trace_path, buf=self.tele.to_json().encode("utf-8")),
                    event_loop,
                )
            except Exception:
                logger.warning(
                    "Failed to persist telemetry trace %r (non-fatal)",
                    trace_path,
                    exc_info=True,
                )
        if self.comm.world_size > 1 and self.take_id is not None:
            import pickle

            try:
                _get_kv_store(self.comm).set(
                    self._key(self.comm.rank), pickle.dumps(self._summary)
                )
            except Exception:
                logger.warning(
                    "Failed to publish telemetry summary (non-fatal)",
                    exc_info=True,
                )

    def apply(self, metadata: SnapshotMetadata) -> bool:
        """Every rank, after the commit barrier's arrive phase (all
        ranks published): fold the cross-rank rollup into THIS rank's
        metadata copy. Rank 0's fold lands in the committed file (and
        tolerates a partial KV read — committing SOME rollup beats
        failing the take); a NON-LEADER whose KV read came back
        incomplete returns False WITHOUT folding, so the caller drops
        its cached copy rather than caching a rollup that diverges from
        the committed file (ADVICE r5 #4). Read-only on the KV store —
        ``cleanup`` deletes the prefix."""
        if self.tele is None:
            # Telemetry-off take: no rank published a summary and the
            # committed file carries no rollup — nothing to fold, and
            # the empty KV prefix must not read as a failed patch.
            return True
        summaries = []
        if self.comm.world_size > 1 and self.take_id is not None:
            import pickle

            try:
                store = _get_kv_store(self.comm)
                blobs = store.try_get_dir(self._prefix())
                for _, raw in sorted((blobs or {}).items()):
                    try:
                        summaries.append(pickle.loads(raw))
                    except Exception:
                        pass
            except Exception:
                blobs = None
                summaries = []
            if (
                self.comm.rank != 0
                and len(summaries) < self.comm.world_size
            ):
                return False
        if not summaries and self._summary is not None:
            summaries = [self._summary]
        try:
            rollup = telemetry.rollup_summaries(summaries)
        except Exception:
            logger.warning("Telemetry rollup failed (non-fatal)", exc_info=True)
            return self.comm.rank == 0
        if rollup:
            metadata.extras = dict(metadata.extras or {})
            metadata.extras["telemetry"] = rollup
        return True

    def cleanup(self) -> None:
        """Leader-only, strictly after the final commit barrier: every
        rank has folded its rollup, delete the take-scoped keys."""
        if self.comm.world_size > 1 and self.take_id is not None:
            try:
                _get_kv_store(self.comm).delete_prefix(self._prefix())
            except Exception:
                logger.debug(
                    "telemetry KV cleanup failed (non-fatal)", exc_info=True
                )

    def discard(self) -> None:
        """Abort path: drop this rank's published summary blob."""
        if self.comm.world_size > 1 and self.take_id is not None:
            try:
                _get_kv_store(self.comm).delete_prefix(self._key(self.comm.rank))
            except Exception:
                pass


# ----------------------------------------------------- degraded commit


class _DegradeContext:
    """Everything the survivors of a rank failure need to finish a
    replicated-only take without the dead rank(s): the fully-annotated
    local manifest (entries carry their checksums once writes drain),
    the partition plan, and this rank's UNSTAGED write requests for
    replicated units assigned to other ranks — identical bytes, so any
    survivor can adopt a dead writer's assignments."""

    def __init__(
        self,
        comm: Communicator,
        take_id: str,
        storage: StoragePlugin,
        event_loop: asyncio.AbstractEventLoop,
        entries: Manifest,
        dropped_replicated: Dict[str, List],
        assignment: Dict[str, int],
        memory_budget: int,
        extras: Optional[Dict[str, Any]],
        pending_io_work: PendingIOWork,
    ) -> None:
        self.comm = comm
        self.take_id = take_id
        self.storage = storage
        self.event_loop = event_loop
        self.entries = entries
        self.dropped_replicated = dropped_replicated
        self.assignment = assignment
        self.memory_budget = memory_budget
        self.extras = extras
        self.pending_io_work = pending_io_work


def _degrade_eligible(per_rank_entries: List[Manifest]) -> Optional[str]:
    """None when every survivor leaf entry is replicated (the SPMD
    program shape proves the dead rank's were too — its bytes exist on
    every survivor); otherwise the reason degrading is impossible. A
    sharded or per-rank-unique entry on any survivor means the dead
    rank held unique partitions whose bytes died with it."""
    from .manifest import PrimitiveEntry

    for entries in per_rank_entries:
        for path, entry in entries.items():
            if is_container_entry(entry):
                continue
            if is_replicated(entry):
                continue
            kind = type(entry).__name__
            if isinstance(entry, PrimitiveEntry):
                return (
                    f"{path!r} is a per-rank primitive (not replicated-"
                    "glob-marked); the dead rank's value is unknowable"
                )
            return f"{path!r} is {kind}: the dead rank held unique state"
    return None


def _degraded_prefix(take_id: str) -> str:
    return f"tpusnap_degraded/{take_id}"


def _maybe_degraded_commit(
    abort_ctx: Optional["_TakeAbortContext"],
    exc: RankFailedError,
) -> Optional[SnapshotMetadata]:
    """Entry point for both commit paths' ``except RankFailedError``:
    returns the committed (degraded) metadata when the policy allows
    and the take is eligible, None when degrade mode is off or the
    failure predates the recovery context. Raises (RankFailedError with
    the eligibility reason, or whatever the degraded protocol hit) when
    degrade was attempted and could not complete — the caller then
    aborts to a torn state exactly as in abort mode."""
    from .knobs import get_rank_failure_policy

    if (
        abort_ctx is None
        or abort_ctx.degrade is None
        or abort_ctx.liveness is None
        or get_rank_failure_policy() != "degrade"
    ):
        return None
    return _degraded_commit(abort_ctx, exc)


def _degraded_commit(
    abort_ctx: "_TakeAbortContext", exc: RankFailedError
) -> SnapshotMetadata:
    """Complete a replicated-only take on the survivor set.

    Pure KV + storage traffic over take-scoped keys (legal from the
    async commit's background thread, independent of the communicator's
    possibly-desynced sequence counters):

    1. every survivor publishes its fully-annotated local manifest and
       meets a survivor-set LinearBarrier (liveness-watched, with the
       acknowledged dead set excluded);
    2. eligibility: every survivor leaf must be replicated — else raise
       (abort to torn; fsck/timeline name the dead rank);
    3. adoption: units the dead rank(s) were assigned are re-planned
       deterministically across the survivors
       (``partitioner.reassign_dead_units``); each adopter stages and
       writes its own identical-bytes copies (journal evidence recorded
       as usual) and publishes the adopted entry versions;
    4. the new leader (min survivor) consolidates the survivor
       manifests, substitutes the adopted entries, records the adoption
       under ``extras["degraded"]``, and commits; a final barrier gates
       journal/KV cleanup.

    All survivors compute every decision from identical gathered inputs
    — no broadcasts. A survivor whose dead-set observation diverges
    (two near-simultaneous failures racing detection) parks in a
    barrier the others never join and aborts at the barrier timeout:
    degraded commit fails safe to torn, never to a wrong manifest."""
    import pickle
    import time as _time

    from . import flight as _flight_mod
    from .partitioner import (
        consolidate_replicated_entries,
        reassign_dead_units,
    )

    ctx = abort_ctx.degrade
    liveness = abort_ctx.liveness
    comm, rank = ctx.comm, ctx.comm.rank
    dead = sorted(set(exc.ranks) | set(liveness.expired()))
    live = sorted(set(range(comm.world_size)) - set(dead))
    if rank not in live or not dead:
        raise exc
    leader = live[0]
    logger.warning(
        "tpusnap degraded commit: rank(s) %s died during take %s; "
        "%d survivor(s) attempting to complete it (leader: rank %d)",
        dead,
        ctx.take_id[:8],
        len(live),
        leader,
    )
    _flight_mod.record("degraded_commit", op="start", dead_ranks=dead)
    kv = _get_kv_store(comm)
    prefix = _degraded_prefix(ctx.take_id)
    watchers = [liveness.watcher(exclude=set(dead))]
    if abort_ctx.monitor is not None:
        watchers.append(abort_ctx.monitor.check)

    def barrier(name: str) -> None:
        b = LinearBarrier(
            store=kv,
            prefix=f"{prefix}/{name}",
            rank=rank,
            world_size=comm.world_size,
            ranks=live,
            watchers=watchers,
        )
        b.arrive()
        b.depart()

    # 0. This rank's writes must be fully drained — the published
    # entries carry their write-path checksums only then.
    if not ctx.pending_io_work.drained():
        ctx.pending_io_work.sync_complete(ctx.event_loop)

    # 1. Publish + gather the survivor manifests.
    kv.set(f"{prefix}/m/{rank}", pickle.dumps(ctx.entries))
    barrier("b1")
    blobs = kv.try_get_dir(f"{prefix}/m/") or {}
    per_rank: List[Manifest] = [{} for _ in range(comm.world_size)]
    got = set()
    for key, raw in blobs.items():
        try:
            r = int(key.rsplit("/", 1)[-1])
        except ValueError:
            continue
        if r in live:
            per_rank[r] = pickle.loads(raw)
            got.add(r)
    for r in live:
        if r not in got:
            # Torn dir listing (the barrier proved the publish): per-key
            # fallback, bounded.
            per_rank[r] = pickle.loads(
                kv.get(f"{prefix}/m/{r}", timeout_sec=120.0)
            )

    # 2. Eligibility — identical verdict on every survivor.
    reason = _degrade_eligible([per_rank[r] for r in live])
    if reason is not None:
        _flight_mod.record("degraded_commit", op="refused", reason=reason)
        raise RankFailedError(
            dead,
            ctx.take_id,
            detail=f"degrade refused: {reason}; aborting to a torn state",
        ) from exc

    # 3. Adoption: deterministic re-plan, then each adopter stages and
    # writes its own replicated copies of the dead writers' units.
    adoption = reassign_dead_units(ctx.assignment, dead, live)
    my_units = sorted(u for u, w in adoption.items() if w == rank)
    my_reqs = [
        wr for u in my_units for wr in ctx.dropped_replicated.get(u, [])
    ]
    if my_reqs:
        adopt_work = sync_execute_write_reqs(
            my_reqs,
            ctx.storage,
            ctx.memory_budget,
            rank,
            ctx.event_loop,
        )
        adopt_work.sync_complete(ctx.event_loop)
    adopted_payload = {}
    for u in adoption:
        if adoption[u] != rank:
            continue
        path, _, chunk = u.partition("::")
        entry = ctx.entries.get(path)
        if entry is None:
            continue
        adopted_payload[u] = entry
    kv.set(f"{prefix}/a/{rank}", pickle.dumps(adopted_payload))
    barrier("b2")

    # 4. Every survivor builds the identical degraded manifest (the
    # leader's copy is the one that commits; the others cache it).
    # Replicated entries consolidate into rank 0's tree — when rank 0
    # itself died, stand the new leader's (SPMD-identical) manifest in
    # for slot 0 so the replicated tree still materializes.
    if 0 in dead:
        per_rank[0] = per_rank[leader]
    global_manifest = consolidate_replicated_entries(per_rank)
    # Same torn-listing defense as the /m/ gather: barrier b2 proved
    # every survivor published, so a rank missing from the dir read
    # gets a bounded per-key fallback — and an unreadable blob RAISES
    # (degrade fails safe to torn) rather than silently committing a
    # manifest missing that adopter's substitutions.
    adopted_blobs = kv.try_get_dir(f"{prefix}/a/") or {}
    adopted_by_rank: Dict[int, bytes] = {}
    for key, raw in adopted_blobs.items():
        try:
            r = int(key.rsplit("/", 1)[-1])
        except ValueError:
            continue
        if r in live:
            adopted_by_rank[r] = raw
    for r in live:
        if r not in adopted_by_rank:
            adopted_by_rank[r] = kv.get(
                f"{prefix}/a/{r}", timeout_sec=120.0
            )
    n_adopted = 0
    for _r, raw in sorted(adopted_by_rank.items()):
        payload = pickle.loads(raw)
        for unit, entry in sorted(payload.items()):
            path, _, chunk = unit.partition("::")
            gkey = f"0/{path}"
            if gkey not in global_manifest:
                continue
            n_adopted += 1
            if chunk:
                # Chunk-grain adoption: substitute only the dead
                # writer's chunk; live writers' chunks keep their
                # (possibly annotated) versions.
                idx = int(chunk)
                cur = global_manifest[gkey]
                if hasattr(cur, "chunks") and idx < len(cur.chunks):
                    cur.chunks[idx] = entry.chunks[idx]
            else:
                # Whole-entry adoption: the authoritative (dead
                # writer's) version may reference a slab or carry stale
                # annotations — the adopter's entry describes the blob
                # it actually wrote.
                global_manifest[gkey] = entry
    extras = dict(ctx.extras or {})
    extras["degraded"] = {
        "dead_ranks": dead,
        "live_ranks": live,
        "adopted_units": sorted(adoption),
        "adopters": {u: w for u, w in sorted(adoption.items())},
    }
    metadata = SnapshotMetadata(
        version=__version__,
        world_size=comm.world_size,
        manifest=global_manifest,
        created_at=_time.time(),
        extras=extras,
    )
    if rank == leader:
        abort_ctx.mark_commit_started()
        _write_metadata(ctx.storage, metadata, ctx.event_loop)
    barrier("b3")
    if rank == leader:
        from .knobs import is_journal_disabled
        from .lifecycle import clear_journal

        if not is_journal_disabled():
            clear_journal(
                ctx.storage,
                ctx.event_loop,
                getattr(ctx.storage, "clear_world_size", comm.world_size),
            )
        if abort_ctx.monitor is not None:
            abort_ctx.monitor.clear()
        if abort_ctx.lease is not None:
            abort_ctx.lease.cleanup()
        # The normal commit's leader cleanup never ran: sweep this
        # take's transport prefixes (late checksums / telemetry
        # summaries some ranks may have published before the death)
        # along with the degraded protocol's own keys.
        for p in (
            prefix + "/",
            f"tpusnap_late_cs/{ctx.take_id}/",
            f"tpusnap_tele/{ctx.take_id}/",
        ):
            try:
                kv.delete_prefix(p)
            except Exception:
                logger.debug("degraded KV cleanup failed", exc_info=True)
    _flight_mod.record(
        "degraded_commit", op="committed", dead_ranks=dead, adopted=n_adopted
    )
    logger.warning(
        "tpusnap degraded commit SUCCEEDED: take %s committed by %d "
        "survivor(s); rank(s) %s's %d replicated unit(s) were adopted",
        ctx.take_id[:8],
        len(live),
        dead,
        n_adopted,
    )
    return metadata


def _record_slo_commit(
    tele: Optional[telemetry.TakeTelemetry],
    metadata: SnapshotMetadata,
    take_id: Optional[str],
    path: str,
    rank: int,
) -> None:
    """Anchor the checkpoint-SLO tracker on a definitive commit (both
    commit paths call this strictly after the metadata write, right
    where ``completed`` is set): close the interval, clear the
    data-at-risk accumulators, refresh the RTO estimate against THIS
    RANK's restore view bytes (what a recovery would actually read),
    and fold the compact ``slo`` section into the summary the history
    event records. Best-effort — never fails a take."""
    try:
        from . import slo as _slo
        from .inspect import rank_payload_nbytes

        snapshot_bytes = rank_payload_nbytes(metadata, rank)
        counters: Dict[str, int] = {}
        incremental = False
        if tele is not None:
            counters = tele.live_snapshot()["counters"]
            incremental = bool(tele.meta.get("incremental"))
        section = _slo.tracker().record_commit(
            take_id or "",
            path,
            snapshot_bytes,
            incremental=incremental,
            counters=counters,
        )
        if tele is not None:
            tele.meta["slo"] = section
    except Exception:
        logger.debug("slo commit record failed", exc_info=True)


def _write_metadata(
    storage: StoragePlugin,
    metadata: SnapshotMetadata,
    event_loop: asyncio.AbstractEventLoop,
) -> None:
    # Atomic (temp+rename on fs): a crash mid-write must not leave a
    # torn metadata file — it would be indistinguishable from corruption.
    # Durability (power-loss survival of the commit) is knob-opted: the
    # fsync after a multi-GB take flushes the storage cache of the whole
    # take (see knobs.is_durable_commit_enabled).
    from .knobs import is_durable_commit_enabled
    from .manifest import encode_metadata

    storage.sync_write_atomic(
        WriteIO(
            path=SNAPSHOT_METADATA_FNAME,
            # Self-checksummed (manifest.encode_metadata): restore/fsck
            # detect a torn or bit-rotted metadata file with a clear
            # MetadataError instead of a JSON traceback.
            buf=encode_metadata(metadata),
        ),
        event_loop,
        durable=is_durable_commit_enabled(),
    )


def load_snapshot(
    path: str,
    rank: int = 0,
    storage_options: Optional[Dict[str, Any]] = None,
    memory_budget_bytes: Optional[int] = None,
) -> Dict[str, Any]:
    """Load a whole snapshot into host memory WITHOUT the original
    program: no statefuls, no target arrays — the nested structure is
    rebuilt from the manifest (dicts/lists/tuples, host numpy leaves,
    primitives). ``rank`` selects the manifest view (replicated entries
    are visible to every rank; sharded entries come back as full dense
    arrays). The debugging/migration companion to ``restore``: inspect a
    checkpoint from a plain REPL, or feed it to another framework.

    Peak memory is the whole selected state plus transient read buffers
    (budget-gated); use ``Snapshot.read_object`` for one value.
    """
    out: Dict[str, Any] = {}
    # Out-of-band single-process tool: the no-op Communicator, NOT
    # get_communicator() — auto-detection inside a live jax.distributed
    # job would turn the budget's hostname gather into a collective that
    # only this rank executes (deadlock).
    budget = memory_budget_bytes or get_process_memory_budget_bytes(
        Communicator()
    )
    with Snapshot(path, storage_options) as snap:
        with snap._op_lock:
            event_loop, storage = snap._resources()
            metadata = snap._get_metadata(storage, event_loop)
            local_manifest = get_manifest_for_rank(metadata, rank)
            top_keys = sorted({p.split("/", 1)[0] for p in local_manifest})
            for key in top_keys:
                key_manifest = {
                    p: e
                    for p, e in local_manifest.items()
                    if p == key or p.startswith(key + "/")
                }
                out[key] = _read_and_inflate(
                    key, key_manifest, {}, storage, budget, rank, event_loop
                )
    return out


def _read_and_inflate(
    key: str,
    key_manifest: Manifest,
    target_flattened: Dict[str, Any],
    storage: StoragePlugin,
    memory_budget: int,
    rank: int,
    event_loop: asyncio.AbstractEventLoop,
    mark: Optional[telemetry.PhaseMarker] = None,
) -> Any:
    """The one read pipeline for a key's manifest subtree: prepare reads
    (against targets when given), batch, execute under the budget,
    inflate. Shared by ``restore`` (targets from the current state_dict,
    which also threads its phase marker) and ``load_snapshot`` (no
    targets, no marker)."""
    from .batcher import batch_read_requests

    read_reqs = []
    futures: Dict[str, Any] = {}
    for logical_path, entry in key_manifest.items():
        if is_container_entry(entry):
            continue
        reqs, fut = prepare_read(
            entry,
            obj_out=target_flattened.get(logical_path),
            logical_path=logical_path,
        )
        read_reqs.extend(reqs)
        futures[logical_path] = fut
    read_reqs = batch_read_requests(read_reqs)
    if mark is not None:
        mark("restore.prepare", then="restore.read", reqs=len(read_reqs))
    sync_execute_read_reqs(read_reqs, storage, memory_budget, rank, event_loop)
    if mark is not None:
        # Storage reads + consume (deserialize/HtoD) under the budget.
        mark("restore.read", then="restore.load", reqs=len(read_reqs))
    flattened = {p: fut.obj for p, fut in futures.items()}
    container_manifest = {
        p: e for p, e in key_manifest.items() if is_container_entry(e)
    }
    return inflate(container_manifest, flattened, prefix=key)


def _load_stateful(
    stateful: Stateful,
    key: str,
    metadata: SnapshotMetadata,
    rank: int,
    storage: StoragePlugin,
    memory_budget: int,
    event_loop: asyncio.AbstractEventLoop,
    mark: Optional[telemetry.PhaseMarker] = None,
) -> None:
    local_manifest = get_manifest_for_rank(metadata, rank)
    local_manifest = {
        p: e
        for p, e in local_manifest.items()
        if p == key or p.startswith(key + "/")
    }
    if not local_manifest:
        logger.warning("No entries for key %r in snapshot; skipping", key)
        return

    # The current state_dict provides restore targets (device placement,
    # shardings, in-place numpy buffers).
    if mark is not None:
        mark.begin("restore.targets")
    target_manifest, target_flattened = flatten(stateful.state_dict(), prefix=key)
    handle_sharded_elasticity(local_manifest, target_flattened)
    if mark is not None:
        mark("restore.targets", then="restore.prepare", key=key)

    restored = _read_and_inflate(
        key,
        local_manifest,
        target_flattened,
        storage,
        memory_budget,
        rank,
        event_loop,
        mark=mark,
    )
    stateful.load_state_dict(restored)
    if mark is not None:
        mark("restore.load", key=key)


# ------------------------------------------------------------- async commit


class _BackgroundWork:
    """Shared scaffold for the background-thread handles (async take's
    commit drain, async restore): daemon thread, exception capture,
    join-and-reraise. Subclasses implement ``_body`` and optionally
    ``_on_error`` / ``_cleanup`` (both run on the background thread)."""

    _thread_name = "tpusnap-bg"

    def _start(self) -> None:
        self._exc: Optional[BaseException] = None
        self._done = threading.Event()
        self._thread = threading.Thread(
            target=self._trampoline, name=self._thread_name, daemon=True
        )
        self._thread.start()

    def _trampoline(self) -> None:
        try:
            self._body()
        except BaseException as e:  # noqa: B902 - re-raised from wait()
            self._exc = e
            try:
                self._on_error(e)
            except Exception:
                pass
        finally:
            try:
                self._cleanup()
            except Exception:
                pass
            self._done.set()

    def _body(self) -> None:
        raise NotImplementedError

    def _on_error(self, exc: BaseException) -> None:
        pass

    def _cleanup(self) -> None:
        pass

    def _join_and_reraise(self) -> None:
        self._thread.join()
        if self._exc is not None:
            raise self._exc

    def done(self) -> bool:
        return self._done.is_set()


class PendingSnapshot(_BackgroundWork):
    """Handle for an in-flight async snapshot (reference snapshot.py:856-944).

    A background thread drains the residual staging windows of a
    pipelined take (interleaved with their storage I/O — see
    scheduler._WriteScheduler) and the remaining writes, then
    synchronizes the commit through a KV-store LinearBarrier — NO
    collectives are allowed off the main thread (reference :902). If any
    rank fails, the error poisons the barrier, ``.snapshot_metadata`` is
    never written, and ``wait()`` re-raises on every rank.
    ``staged()``/``wait_staged()`` expose the staging-complete boundary
    (content frozen); ``wait()`` the committed snapshot.
    """

    # Historically a 1800.0 literal (reference snapshot.py:857); now
    # 3x TPUSNAP_BARRIER_TIMEOUT_S (knobs.get_commit_barrier_timeout_s),
    # resolved at construction.
    _thread_name = "tpusnap-commit"

    def __init__(
        self,
        path: str,
        pending_io_work: PendingIOWork,
        metadata: SnapshotMetadata,
        storage: StoragePlugin,
        comm: Communicator,
        event_loop: asyncio.AbstractEventLoop,
        storage_options: Optional[Dict[str, Any]] = None,
        late_checksums: Optional["_LateChecksums"] = None,
        abort_ctx: Optional["_TakeAbortContext"] = None,
        tele_commit: Optional["_TelemetryCommit"] = None,
    ) -> None:
        self.path = path
        self._pending_io_work = pending_io_work
        self._metadata = metadata
        self._storage = storage
        self._comm = comm
        self._event_loop = event_loop
        self._storage_options = storage_options
        self._late_checksums = late_checksums
        self._abort_ctx = abort_ctx
        self._tele_commit = tele_commit
        self._snapshot: Optional[Snapshot] = None

        # Barrier identity must be agreed on the MAIN thread (this may
        # broadcast); the background thread then only touches the KV store.
        barrier_prefix = f"tpusnap_commit/{uuid.uuid4().hex}"
        barrier_prefix = comm.broadcast_object(barrier_prefix, src=0)
        # GC proof point: the commit barrier will prove consumption of
        # everything pending NOW; collectives the main thread issues
        # later (a newer take on the same communicator) stay pending.
        self._gc_epoch = comm.gc_epoch()
        from .knobs import get_commit_barrier_timeout_s

        commit_timeout = get_commit_barrier_timeout_s()
        # Peer abort records surface as TakeAbortedError — and a dead
        # peer's lease expiry as RankFailedError — from the background
        # commit's barrier waits within seconds.
        watchers = (
            abort_ctx.barrier_watchers() if abort_ctx is not None else None
        )
        self._barrier = LinearBarrier(
            store=_get_kv_store(comm),
            prefix=barrier_prefix,
            rank=comm.rank,
            world_size=comm.world_size,
            timeout_sec=commit_timeout,
            watchers=watchers or None,
        )
        # The cleanup gate (ADVICE r5 #4): after the commit barrier's
        # depart, every rank patches its local manifest copy from the
        # take-scoped KV blobs; this second barrier proves every rank
        # has READ them before rank 0 deletes the prefix.
        self._post_barrier = (
            LinearBarrier(
                store=_get_kv_store(comm),
                prefix=barrier_prefix + "-post",
                rank=comm.rank,
                world_size=comm.world_size,
                timeout_sec=commit_timeout,
                watchers=watchers or None,
            )
            if comm.world_size > 1
            else None
        )
        # The main thread is done with collectives for this take; free
        # the communicator's wait watcher for any newer take. The
        # background commit keeps abort awareness via the barrier
        # watcher above.
        if abort_ctx is not None:
            abort_ctx.disarm()
        # The background commit synchronizes through the LinearBarrier,
        # not the communicator — point the stall watchdog's straggler
        # attribution at its arrive keys.
        if tele_commit is not None and tele_commit.progress is not None:
            tele_commit.progress.add_attribution(self._barrier.current_missing)
        # Control is about to return to training: release the recorder's
        # process-global slot (a newer take may install its own); the
        # background drain records through captured references + the
        # thread-local overlay in _body.
        if tele_commit is not None and tele_commit.tele is not None:
            # The blocked window (take start → control returns here):
            # the one number async_take exists to minimize, recorded
            # before the background thread starts so the summary/history
            # field is never mutated concurrently. Regression-gated via
            # `tpusnap history --check --metric async_blocked_s`.
            tele = tele_commit.tele
            blocked_s = tele.now()
            tele.meta["async_blocked_s"] = round(blocked_s, 6)
            tele.record_span("async_blocked", 0.0, blocked_s)
            telemetry.release_global(tele)
            # From here to the take's end a watched take samples what
            # holds this thread, the caller's, and the drain's loop.
            tele.watch_begin()
        self._start()

    def _body(self) -> None:
        # A RankFailedError from the barrier waits here ordinarily
        # takes the normal abort path (_on_error): a plain async take
        # never runs the degraded commit — the caller may mutate
        # host-aliasing state the moment async_take returns, so
        # adoption's re-staging could capture post-return bytes (the
        # degrade context is not armed for it). Stream captures
        # (`_stream_capture=True`) DO arm it — their force-cloned
        # staging froze take-owned copies of every byte, so adoption
        # re-stages capture-time state regardless of what the caller
        # does after return — and the handler below completes the
        # micro-commit on the survivors. A failed or refused degrade
        # re-raises into the normal abort path (torn, salvageable).
        tele = self._tele_commit.tele if self._tele_commit is not None else None
        with telemetry.use(tele):
            try:
                self._body_impl()
            except RankFailedError as rank_exc:
                degraded_meta = _maybe_degraded_commit(
                    self._abort_ctx, rank_exc
                )
                if degraded_meta is None:
                    raise
                self._commit_degraded(degraded_meta)

    def _commit_degraded(self, metadata: SnapshotMetadata) -> None:
        # Mirror of the sync take's degraded tail: the survivor-set
        # protocol already wrote the metadata and cleared the journal;
        # this rank only records the commit and builds the handle.
        # Storage/event-loop teardown stays in _cleanup, as on the
        # normal path.
        self._metadata = metadata
        ctx = self._abort_ctx
        assert ctx is not None and ctx.degrade is not None
        try:
            self._comm.gc_consumed_keys(self._gc_epoch)
        except Exception:
            pass
        if self._tele_commit is not None:
            if self._tele_commit.tele is not None:
                self._tele_commit.tele.meta["completed"] = True
            _record_slo_commit(
                self._tele_commit.tele,
                metadata,
                ctx.degrade.take_id,
                self.path,
                self._comm.rank,
            )
            self._tele_commit.finish_progress()
        from . import flight as _flight_mod

        _flight_mod.recorder().end_take("committed")
        snapshot = Snapshot(self.path, self._storage_options, self._comm)
        # Every survivor built the identical degraded manifest.
        snapshot._metadata = metadata
        self._snapshot = snapshot

    def _body_impl(self) -> None:
        tele = self._tele_commit.tele if self._tele_commit is not None else None
        drain_start = tele.now() if tele is not None else 0.0
        self._pending_io_work.sync_complete(self._event_loop)
        if tele is not None:
            tele.record_span(
                "io_drain", drain_start, tele.now() - drain_start, phase=True
            )
        prep_start = tele.now() if tele is not None else None
        from .knobs import is_durable_commit_enabled

        if is_durable_commit_enabled():
            # Per-rank dirent durability before the commit barrier (see
            # the sync take's identical step).
            self._storage.sync_flush_created_dirs(self._event_loop)
        if self._late_checksums is not None:
            # Writes drained: publish this rank's deferred checksums
            # (pure KV traffic — legal off the main thread, like the
            # barrier itself).
            self._late_checksums.publish()
        if self._tele_commit is not None:
            # Writes drained: persist this rank's trace + publish its
            # summary before the commit barrier (metadata still last).
            self._tele_commit.persist(
                self._storage, self._event_loop, self._abort_ctx, prep_start
            )
        self._barrier.arrive()
        if self._comm.rank == 0:
            # arrive() returned ⟹ every rank arrived ⟹ every rank
            # published: patch the gathered manifest (one dir-get),
            # commit. The keys outlive the commit until the post
            # barrier proves every rank has read them.
            if self._late_checksums is not None:
                self._late_checksums.apply(self._metadata.manifest)
            if self._tele_commit is not None:
                self._tele_commit.apply(self._metadata)
            if self._abort_ctx is not None:
                self._abort_ctx.mark_commit_started()
            _write_metadata(self._storage, self._metadata, self._event_loop)
        self._barrier.depart()
        # depart() returned ⟹ the leader observed every arrival ⟹
        # every rank published: non-leaders patch their local manifest
        # copies too (one dir-get each), so every rank's handle carries
        # cached, fully-patched metadata instead of paying a metadata
        # GET on first access (ADVICE r5 #4). Best-effort — a failed
        # patch falls back to the lazy committed-file read.
        meta_cached = True
        if self._comm.rank != 0:
            try:
                if self._late_checksums is not None:
                    self._late_checksums.apply(self._metadata.manifest)
                if self._tele_commit is not None and not self._tele_commit.apply(
                    self._metadata
                ):
                    # Incomplete KV read: don't cache a rollup that
                    # diverges from the committed file.
                    meta_cached = False
            except Exception:
                logger.warning(
                    "Non-leader late-checksum patch failed; falling back "
                    "to reading committed metadata (non-fatal)",
                    exc_info=True,
                )
                meta_cached = False
        if self._post_barrier is not None:
            # Every rank arriving here has read the take-scoped KV
            # blobs; rank 0's arrive() returns once all have, gating
            # the deletes.
            self._post_barrier.arrive()
            if self._comm.rank == 0:
                if self._late_checksums is not None:
                    self._late_checksums.cleanup()
                if self._tele_commit is not None:
                    self._tele_commit.cleanup()
            self._post_barrier.depart()
        if self._comm.rank == 0:
            # Commit done (see the sync take's identical step): clear
            # the take journal, strictly after the metadata write.
            from .knobs import is_journal_disabled
            from .lifecycle import clear_journal

            if not is_journal_disabled():
                clear_journal(
                    self._storage,
                    self._event_loop,
                    getattr(
                        self._storage,
                        "clear_world_size",
                        self._comm.world_size,
                    ),
                )
            if (
                self._abort_ctx is not None
                and self._abort_ctx.monitor is not None
            ):
                self._abort_ctx.monitor.clear()
            if (
                self._abort_ctx is not None
                and self._abort_ctx.lease is not None
            ):
                self._abort_ctx.lease.cleanup()
        # Every rank departing proves it consumed the take's gathers
        # and the barrier-prefix broadcast; release their KV keys now
        # — no further barrier will run on this communicator, so the
        # lazy GC would otherwise never fire (and per-iteration
        # manifests would accumulate in the coordination service
        # forever). Bounded by the epoch captured at construction so
        # a newer take's in-flight keys are never touched. KV deletes
        # only — still no collectives off the main thread.
        try:
            self._comm.gc_consumed_keys(self._gc_epoch)
        except Exception:
            pass
        if self._tele_commit is not None:
            if self._tele_commit.tele is not None:
                # Commit done: eligible for the cross-run history when
                # _cleanup's end_take publishes the summary, and the
                # SLO tracker's RPO clock re-anchors here.
                self._tele_commit.tele.meta["completed"] = True
            _record_slo_commit(
                self._tele_commit.tele,
                self._metadata,
                self._tele_commit.take_id,
                self.path,
                self._comm.rank,
            )
            self._tele_commit.finish_progress()
        from . import flight as _flight_mod

        _flight_mod.recorder().end_take("committed")
        snapshot = Snapshot(self.path, self._storage_options, self._comm)
        if meta_cached:
            # Fully patched on every rank (late checksums + telemetry
            # rollup applied locally) — cache it; the rare failed
            # non-leader patch lazily reads the committed file instead.
            snapshot._metadata = self._metadata
        self._snapshot = snapshot

    def _on_error(self, exc: BaseException) -> None:
        # Publish this rank's abort record (peers' barrier watchers then
        # raise TakeAbortedError) and best-effort delete its staged
        # blobs; the metadata is never written. Without a monitor
        # (single-process, or explicit comm without abort context), fall
        # back to poisoning the barrier the classic way.
        ctx = self._abort_ctx
        if ctx is not None:
            ctx.on_failure(exc)
            if ctx.monitor is not None:
                return
        self._barrier.report_error(exc)

    def _cleanup(self) -> None:
        if self._tele_commit is not None:
            # Failure paths stopped it with an "aborted" record already
            # (abort_ctx.on_failure); this is the idempotent safety net.
            self._tele_commit.stop_progress()
        self._storage.sync_close(self._event_loop)
        self._event_loop.close()
        if self._tele_commit is not None and self._tele_commit.tele is not None:
            telemetry.end_take(self._tele_commit.tele)

    def staged(self) -> bool:
        """Whether the snapshot content is frozen — safe for the caller
        to mutate host-aliasing state IN PLACE (raw numpy buffers,
        pinned_host donation) and to run a step that DONATES device
        state. Functional JAX updates that donate nothing never need
        this — the stagers hold references. A device array donated
        (deleted) before it was staged fails the take by the leaf's
        name, at every state size: accelerator-resident leaves are
        never in the blocked window.

        This is staging-complete (no staged buffer is memory the
        caller can write or delete) wherever no stager of the take went
        copy-on-write: true at construction for non-pipelined takes
        (incremental, or window 0); pipelined takes stage their
        accelerator-resident leaves, and what the window did not hold
        of the rest, on the background drain. A stager that went
        copy-on-write (TPUSNAP_ASYNC_COW, the default: a numpy leaf, a
        ``pinned_host`` or CPU-backend array, or a slab with such a
        member) wrote or writes its blob from the live bytes and
        verifies it after, so a take with one reports THIS RANK's
        write-drain boundary instead (strictly earlier than the
        cross-rank commit barrier). Which of the two is read off what
        the take did (``PendingIOWork.safe_to_mutate``), not off the
        knob: an accelerator's leaves never go copy-on-write, and a
        trainer that donates them waits for their bytes to reach the
        host, not for storage. The rendezvous CONTRACT (staged() ⟹ safe
        to mutate, safe to donate) holds either way."""
        return self._pending_io_work.safe_to_mutate()

    def wait_staged(self, timeout: Optional[float] = None) -> bool:
        """Block until :meth:`staged` is True (or ``timeout`` elapses;
        returns whether the content froze). Re-raises the background
        failure if the drain died before staging finished — otherwise a
        crashed drain would turn this into a silent infinite wait."""
        import time as _time

        deadline = None if timeout is None else _time.monotonic() + timeout
        # The thread that waits here runs no step: the drain starts no
        # copy on the chip for its sake meanwhile (scheduler._steps_may_run).
        with self._pending_io_work.caller_waits():
            while True:
                step = 0.05
                if deadline is not None:
                    remaining = deadline - _time.monotonic()
                    if remaining <= 0:
                        return self.staged()
                    step = min(step, remaining)
                # Staging first; by then every stager has said whether
                # it went copy-on-write, and only then the drain.
                io_work = self._pending_io_work
                if io_work.wait_staged(step) and (
                    not io_work.went_cow() or io_work.wait_drained(step)
                ):
                    return True
                if self.done():
                    self._join_and_reraise()
                    return self.staged()

    def wait(self) -> Snapshot:
        self._join_and_reraise()
        assert self._snapshot is not None
        return self._snapshot


class PendingRestore(_BackgroundWork):
    """Handle for an in-flight background restore (``async_restore``).

    ``wait()`` joins the thread and re-raises any failure; the restored
    ``app_state`` must not be read before it returns. The snapshot
    handle's ``_op_lock`` serializes against concurrent
    restore/read_object/verify calls on the same handle."""

    _thread_name = "tpusnap-restore"

    def __init__(
        self,
        snapshot: Snapshot,
        app_state: AppState,
        comm: Communicator,
        memory_budget: int,
    ) -> None:
        self._snapshot = snapshot
        self._app_state = app_state
        self._comm = comm
        self._memory_budget = memory_budget
        self._caller = telemetry.thread_key()
        self._start()

    def _body(self) -> None:
        with self._snapshot._op_lock:
            self._snapshot._restore_locked(
                self._app_state,
                self._comm,
                per_key_barrier=False,
                memory_budget=self._memory_budget,
                caller=self._caller,
            )

    def wait(self) -> None:
        self._join_and_reraise()


def _get_kv_store(comm: Communicator) -> KVStore:
    if comm.world_size == 1:
        return MemoryKVStore()
    return CoordinationKVStore()
