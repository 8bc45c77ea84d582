"""Metadata collectives over the JAX distributed coordination service.

TPU-native counterpart of /root/reference/torchsnapshot/pg_wrapper.py.
The reference funnels small-object collectives (all_gather_object,
broadcast_object_list, barrier) through torch.distributed (gloo/NCCL).
tpusnap instead rides the **coordination-service KV store** that
``jax.distributed.initialize`` brings up over DCN:

- it exists on every multi-host TPU deployment (no extra rendezvous);
- it is usable from background threads, where device collectives are
  forbidden (same constraint as the reference, snapshot.py:902);
- manifests/globs/write-loads are KB-scale — device collectives over ICI
  would be overkill (SURVEY.md §5).

Like the reference's PGWrapper (pg_wrapper.py:15-30), construction
auto-detects the environment: single process → no-op collectives; a live
``jax.distributed`` coordination client with >1 process → KV-store-backed
collectives. Detection reads the coordination state directly so that
checkpointing host-resident state never initializes a device backend.

Sequencing: keys are namespaced per Communicator *instance* (assigned
lazily at the first collective from a process-global counter — ranks
issue their first collective on instances in the same order under SPMD,
while collective-free construction on rank subsets stays free) and
sequenced per instance, so two interleaved Communicator instances can
never cross-wire keys. Within one instance, ranks must execute the same
collectives in the same order — the same contract as any collective
backend.

Scalability: ``all_gather_object`` is one KV set + one barrier + one
``key_value_dir_get`` per rank — O(1) RPCs regardless of world size
(the reference pays one torch.dist gather; the naive KV port paid
world_size serial gets). ``broadcast_object`` is one set / one blocking
get with NO barrier. Consumed keys are garbage-collected lazily: rank 0
deletes a collective's prefix only after a later barrier proves every
rank has moved past it.
"""

from __future__ import annotations

import base64
import logging
import pickle
import threading
from typing import Any, List, Optional

logger = logging.getLogger(__name__)


def _default_timeout_ms() -> int:
    # Historically a 600_000 literal (mirroring reference
    # dist_store.py:17); now routed through the one knob that bounds
    # every blocking collective wait. Resolved per-instance so test
    # overrides apply without reimports.
    from .knobs import get_barrier_timeout_s

    return int(get_barrier_timeout_s() * 1000.0)


class Communicator:
    """Uniform interface; base class doubles as the single-process no-op
    implementation (reference pg_wrapper.py single-process path)."""

    @property
    def rank(self) -> int:
        return 0

    @property
    def world_size(self) -> int:
        return 1

    def barrier(self) -> None:
        return None

    def all_gather_object(self, obj: Any) -> List[Any]:
        return [obj]

    def broadcast_object(self, obj: Any, src: int = 0) -> Any:
        return obj

    def gc_epoch(self) -> int:
        """Marker for ``gc_consumed_keys``: keys pending GC as of now."""
        return 0

    def gc_consumed_keys(self, epoch: Optional[int] = None) -> None:
        """Release KV keys of fully-consumed collectives — the first
        ``epoch`` pending ones (from a prior ``gc_epoch()`` call), or
        all when ``epoch`` is None. Callers must hold external proof
        that EVERY rank consumed those keys (e.g. all ranks departed a
        LinearBarrier issued after the collective) — async_take's
        background commit uses this, since it never issues another
        barrier on the communicator. The epoch bound keeps a background
        flush from deleting keys of collectives the main thread started
        AFTER the proof point. Pure KV deletes: safe from any thread."""
        return None

    def set_wait_watcher(self, watcher) -> None:
        """Install a callable run periodically inside every collective
        wait; it may raise to abort the wait early (take-abort
        propagation). While installed, barriers and blocking gets switch
        from the coordination service's native blocking RPCs (which
        cannot be interrupted before their timeout) to KV polling. ALL
        ranks must install/clear at the same point in their collective
        program — the polling barrier only interoperates with itself.
        No-op on the single-process communicator."""
        return None

    def clear_wait_watcher(self) -> None:
        return None

    def barrier_missing_ranks(self) -> Optional[List[int]]:
        """While this process is blocked inside a POLLING barrier (the
        abort-aware mode every multi-process take runs in), the sorted
        rank ids whose arrive keys are absent — the stall watchdog's
        straggler attribution. None when not waiting in a barrier, or
        when the wait mode cannot be introspected (native
        wait_at_barrier). Called from the watchdog thread: pure KV
        reads, safe concurrently with the waiting thread's polling."""
        return None


_instance_count = 0


def _next_instance() -> int:
    global _instance_count
    _instance_count += 1
    return _instance_count


class JaxCoordinationComm(Communicator):
    """KV-store-backed collectives for multi-process jobs."""

    def __init__(
        self,
        timeout_ms: Optional[int] = None,
        namespace: Optional[str] = None,
    ) -> None:
        from jax._src import distributed

        client = distributed.global_state.client
        if client is None:
            raise RuntimeError(
                "jax.distributed is not initialized; call "
                "jax.distributed.initialize() before using tpusnap across "
                "processes"
            )
        self._client = client
        # Read rank/world from the coordination state, not
        # jax.process_index()/process_count() — those initialize the device
        # backend, which checkpointing of host state must never require.
        self._rank = distributed.global_state.process_id
        self._world_size = distributed.global_state.num_processes
        self._timeout_ms = (
            timeout_ms if timeout_ms is not None else _default_timeout_ms()
        )
        # Keys are namespaced per instance so interleaved use of two
        # Communicator objects cannot cross-wire. Auto namespaces are
        # assigned LAZILY at the first collective — constructing a
        # communicator for collective-free work (restore, read_object)
        # on a subset of ranks must not desync the counter that makes
        # namespaces agree across ranks. Ranks must issue their FIRST
        # collective on instances in the same order (SPMD); pass
        # ``namespace`` explicitly when that order may diverge.
        # Explicit namespaces live under "u/" with unsafe characters
        # escaped, so they can never collide with an auto namespace
        # ("i<N>") nor map onto another namespace's barrier ids.
        self._ns: Optional[str] = (
            f"tpusnap/u/{_sanitize_ns(namespace)}"
            if namespace is not None
            else None
        )
        self._seq = 0
        # Prefixes fully consumed on this rank, deletable (by rank 0)
        # once a later barrier proves every rank has moved past them.
        # Guarded by a lock: the async-commit background thread flushes
        # while the main thread may be appending for a newer take.
        self._gc_pending: List[str] = []
        self._gc_lock = threading.Lock()
        # Optional abort watcher (see Communicator.set_wait_watcher).
        self._wait_watcher = None
        # ("barrier", prefix) while blocked in a polling barrier — read
        # by barrier_missing_ranks() from the watchdog thread. A plain
        # attribute write (GIL-atomic); staleness across the hand-off is
        # tolerable for a best-effort diagnostic.
        self._live_wait: Optional[tuple] = None

    @property
    def rank(self) -> int:
        return self._rank

    @property
    def world_size(self) -> int:
        return self._world_size

    def _namespace(self) -> str:
        if self._ns is None:
            self._ns = f"tpusnap/i{_next_instance()}"
        return self._ns

    def _next_seq(self) -> int:
        self._seq += 1
        return self._seq

    def _flush_gc(self, upto: Optional[int] = None) -> None:
        """Delete pending prefixes whose consumption has been proved
        global — the first ``upto`` of them, or all when None. Called
        right after a successful wait_at_barrier (all pending), or from
        the async commit with an epoch captured at its proof point."""
        with self._gc_lock:
            if upto is None:
                flush, self._gc_pending = self._gc_pending, []
            else:
                flush = self._gc_pending[:upto]
                self._gc_pending = self._gc_pending[upto:]
        if self._rank != 0:
            return
        for prefix in flush:
            try:
                self._client.key_value_delete(prefix)
            except Exception:
                # Best-effort gc of proved-consumed KV prefixes; a leaked
                # key costs service memory, not correctness.
                logger.debug(
                    "coordination-KV gc delete failed for %r", prefix,
                    exc_info=True,
                )

    def set_wait_watcher(self, watcher) -> None:
        self._wait_watcher = watcher

    def clear_wait_watcher(self) -> None:
        self._wait_watcher = None

    def barrier(self) -> None:
        from . import telemetry

        with telemetry.span("comm.barrier", kind=telemetry.WAIT):
            self._barrier_impl()

    def _barrier_impl(self) -> None:
        from . import flight

        seq = self._next_seq()
        # Flight-recorder anchor: every rank logs the SAME anchor string
        # for the same barrier, and the exit event fires at (nearly) the
        # same instant on all ranks — the cross-rank clock-skew
        # alignment `tpusnap timeline` runs on.
        anchor = f"{self._namespace()}/b{seq}"
        flight.record("barrier_enter", op=anchor)
        if self._wait_watcher is not None:
            # Abort-aware mode: the native wait_at_barrier blocks inside
            # the coordination client until its timeout and cannot
            # observe an abort record. Substitute a KV polling barrier
            # (arrive keys + a depart key, LinearBarrier-style) that
            # runs the watcher every poll. All ranks take this branch
            # for the same seq because watcher installation is a fixed
            # point in the take's SPMD program.
            prefix = self._polling_barrier(seq)
            flight.record("barrier_exit", op=anchor)
            # Flush BEFORE registering this barrier's own prefix: the
            # flush must never delete the depart key a slow rank is
            # still polling — this prefix is only provably consumed
            # after the NEXT barrier.
            self._flush_gc()
            with self._gc_lock:
                self._gc_pending.append(prefix + "/")
            return
        # Namespace components contain no "." (auto ids are digits,
        # explicit ones are sanitized), so this mapping is injective —
        # distinct namespaces can never satisfy each other's barriers.
        self._client.wait_at_barrier(
            anchor.replace("/", "."),
            timeout_in_ms=self._timeout_ms,
        )
        flight.record("barrier_exit", op=anchor)
        self._flush_gc()

    def _watched_wait_key(self, key: str, deadline: float):
        """Poll ``key`` until present (returning its value), running the
        wait watcher (which may raise) every iteration."""
        import time

        from .dist_store import _client_try_get

        while True:
            watcher = self._wait_watcher
            if watcher is not None:
                watcher()
            # The probe blocks up to its own 50ms timeout on older
            # clients without key_value_try_get, doubling as the poll
            # interval there.
            value = _client_try_get(self._client, key)
            if value is not None:
                return value
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"Timed out waiting for coordination key {key!r}"
                )
            time.sleep(0.05)

    def _polling_barrier(self, seq: int) -> str:
        """KV-polling two-phase barrier, interoperable only with itself:
        every rank sets an arrive key; rank 0 collects them and sets the
        depart key; non-leaders wait for depart. Returns the key prefix;
        the caller registers it for GC after a LATER barrier proves
        every rank has passed this one (the same lazy proof as
        collective payload keys — deleting the depart key any earlier
        could strand a slow rank).

        Deliberately NOT dist_store.LinearBarrier: that rides
        CoordinationKVStore, whose keys live under its own store prefix
        — outside this communicator's namespace, invisible to the
        _gc_pending raw-client deletes that keep per-take keys from
        accumulating in the coordination service for the job's
        lifetime. Keeping the barrier on raw client keys inside
        ``{ns}/`` makes the existing GC proof cover it for free."""
        import time

        prefix = f"{self._namespace()}/pb{seq}"
        deadline = time.monotonic() + self._timeout_ms / 1000.0
        self._client.key_value_set(f"{prefix}/a/{self._rank}", "1")
        self._live_wait = ("barrier", prefix)
        try:
            if self._rank == 0:
                for r in range(1, self._world_size):
                    self._watched_wait_key(f"{prefix}/a/{r}", deadline)
                self._client.key_value_set(f"{prefix}/d", "1")
            else:
                self._watched_wait_key(f"{prefix}/d", deadline)
        finally:
            self._live_wait = None
        return prefix

    def barrier_missing_ranks(self) -> Optional[List[int]]:
        live = self._live_wait
        if live is None or live[0] != "barrier":
            return None
        try:
            entries = self._client.key_value_dir_get(f"{live[1]}/a")
        except Exception:
            return None
        arrived = set()
        for key, _value in entries:
            try:
                arrived.add(int(key.rsplit("/", 1)[-1]))
            except ValueError:
                continue
        missing = sorted(set(range(self._world_size)) - arrived)
        if not missing:
            # Everyone arrived but we are still waiting: a non-leader is
            # blocked on the depart key, which rank 0 owns — attribute
            # the stall to the leader (mirrors LinearBarrier).
            return [0] if self._rank != 0 else None
        return missing

    def gc_epoch(self) -> int:
        with self._gc_lock:
            return len(self._gc_pending)

    def gc_consumed_keys(self, epoch: Optional[int] = None) -> None:
        self._flush_gc(upto=epoch)

    def all_gather_object(self, obj: Any) -> List[Any]:
        """One KV set + one barrier + ONE dir-get — O(1) RPCs per rank
        regardless of world size (the per-rank serial gets of the naive
        port serialized take/restore at scale)."""
        from . import telemetry

        with telemetry.span("comm.all_gather", kind=telemetry.WAIT):
            return self._all_gather_object_impl(obj)

    def _all_gather_object_impl(self, obj: Any) -> List[Any]:
        seq = self._next_seq()
        prefix = f"{self._namespace()}/ag{seq}"
        self._client.key_value_set(f"{prefix}/{self._rank}", _encode(obj))
        # The barrier guarantees every rank's key is written (and lets
        # rank 0 GC prefixes from earlier collectives).
        self.barrier()
        entries = self._client.key_value_dir_get(prefix)
        by_rank = {}
        for key, raw in entries:
            by_rank[int(key.rsplit("/", 1)[-1])] = raw
        if len(by_rank) != self._world_size:
            raise RuntimeError(
                f"all_gather {prefix!r}: expected {self._world_size} "
                f"entries, got {sorted(by_rank)}"
            )
        with self._gc_lock:
            self._gc_pending.append(prefix + "/")
        return [_decode(by_rank[r]) for r in range(self._world_size)]

    def broadcast_object(self, obj: Any, src: int = 0) -> Any:
        """One set (src) / one blocking get (others); no barrier. The key
        is GC'd after a later barrier proves global consumption."""
        from . import telemetry

        with telemetry.span("comm.broadcast", kind=telemetry.WAIT):
            return self._broadcast_object_impl(obj, src)

    def _broadcast_object_impl(self, obj: Any, src: int = 0) -> Any:
        seq = self._next_seq()
        key = f"{self._namespace()}/bc{seq}"
        if self._rank == src:
            self._client.key_value_set(key, _encode(obj))
            result = obj
        elif self._wait_watcher is not None:
            # Abort-aware wait: the native blocking get cannot be
            # interrupted before its timeout; poll instead, running the
            # watcher (which may raise) each iteration.
            import time

            result = _decode(
                self._watched_wait_key(
                    key, time.monotonic() + self._timeout_ms / 1000.0
                )
            )
        else:
            result = _decode(
                self._client.blocking_key_value_get(key, self._timeout_ms)
            )
        if self._rank == 0:
            with self._gc_lock:
                self._gc_pending.append(key)
        return result


class SubsetComm(JaxCoordinationComm):
    """Collectives over a SUBSET of the jax.distributed world — the
    communicator elastic delta streams run their per-epoch captures on
    (:mod:`tpusnap.delta`): after a rank dies or leaves, the survivors
    keep taking real multi-rank snapshots without it, and a joiner is
    folded in at the next epoch simply by listing it as a member.

    The subset is expressed by RE-RANKING: ``rank``/``world_size``
    report this process's position within ``members`` (sorted global
    process ids), so every loop the parent class runs over
    ``range(world_size)`` — arrive keys, gather slots, leader checks —
    stays correct verbatim. The GLOBAL identity survives as
    ``global_rank``/``global_ranks`` for rendering and forensics (take
    internals — leases, journals, manifests — speak virtual ranks; the
    epoch metadata maps them back).

    Two contract changes against the parent:

    - the namespace is REQUIRED and must be identical (and unique per
      epoch) on every member — the lazy auto-counter cannot agree
      across processes that construct different numbers of
      communicators once the world diverges;
    - barriers always use the KV polling path: the coordination
      service's native ``wait_at_barrier`` counts every process in the
      job, which would park a subset barrier until the full-world
      timeout.
    """

    def __init__(
        self,
        members: List[int],
        namespace: str,
        timeout_ms: Optional[int] = None,
    ) -> None:
        super().__init__(timeout_ms=timeout_ms, namespace=namespace)
        self.global_rank = self._rank
        self.global_ranks = sorted(int(m) for m in members)
        if len(set(self.global_ranks)) != len(self.global_ranks):
            raise ValueError(f"duplicate members: {members}")
        if self.global_rank not in self.global_ranks:
            raise ValueError(
                f"process {self.global_rank} is not a member of {members}"
            )
        self._rank = self.global_ranks.index(self.global_rank)
        self._world_size = len(self.global_ranks)

    def _barrier_impl(self) -> None:
        from . import flight

        seq = self._next_seq()
        anchor = f"{self._namespace()}/b{seq}"
        flight.record("barrier_enter", op=anchor)
        prefix = self._polling_barrier(seq)
        flight.record("barrier_exit", op=anchor)
        # Same GC ordering as the parent's watched branch: flush proved
        # prefixes BEFORE registering this barrier's own.
        self._flush_gc()
        with self._gc_lock:
            self._gc_pending.append(prefix + "/")


def _sanitize_ns(ns: str) -> str:
    """Escape everything outside [A-Za-z0-9_-]: keeps user namespaces
    from colliding with each other or with key/barrier separators."""
    import re

    return re.sub(
        r"[^A-Za-z0-9_-]", lambda m: f"%{ord(m.group(0)):02x}", ns
    )


def _encode(obj: Any) -> str:
    return base64.b64encode(pickle.dumps(obj)).decode("ascii")


def _decode(raw) -> Any:
    if isinstance(raw, bytes):
        raw = raw.decode("ascii")
    return pickle.loads(base64.b64decode(raw))


def get_communicator(comm: Optional[Communicator] = None) -> Communicator:
    """Auto-detect (reference pg_wrapper.py:15-30): explicit comm wins; a
    live multi-process jax.distributed runtime selects the KV-backed
    implementation; otherwise single-process no-op.

    Detection deliberately reads ``jax.distributed``'s coordination state
    instead of calling ``jax.process_count()``: the latter initializes the
    device backend, which is slow (and can block on flaky hardware links)
    — and a snapshot of host-resident state must not require a device at
    all. Multi-process JAX always goes through
    ``jax.distributed.initialize``, so the coordination client is the
    authoritative signal."""
    if comm is not None:
        return comm
    try:
        from jax._src import distributed as _jd

        client = _jd.global_state.client
        nproc = _jd.global_state.num_processes or 1
    except Exception:
        # The private coordination-state API moved (JAX internals carry no
        # stability guarantee). JaxCoordinationComm needs that API too, so
        # there is no degraded mode — but silently treating a multi-host
        # job as single-process would corrupt snapshots, so probe the
        # public API (slower: initializes the device backend) and fail
        # loudly if this really is a multi-process job.
        import jax

        if jax.process_count() > 1:
            raise RuntimeError(
                "tpusnap cannot reach JAX's distributed coordination "
                "client on this JAX version (jax._src.distributed moved); "
                "multi-process snapshots would be corrupted. Pass an "
                "explicit `comm` or update tpusnap."
            )
        return Communicator()

    if client is not None and nproc > 1:
        return JaxCoordinationComm()

    if client is None and _backend_initialized() is not False:
        # Some multi-host deployments (libtpu auto-bootstrap on TPU pods)
        # never call jax.distributed.initialize, so there is no
        # coordination client to ride. A device backend is already live
        # (device-array snapshots imply it is), so probing process_count
        # costs no new backend init — and a >1 answer with no client means
        # snapshots would collide: fail loudly. With no backend
        # initialized we stay backend-free and treat the process as
        # single-process. "Unknown" (the probe itself broke) must run the
        # loud check too: assuming single-process here is the silent
        # snapshot-collision corruption mode.
        import jax

        if jax.process_count() > 1:
            raise RuntimeError(
                "This looks like a multi-host JAX job without "
                "jax.distributed.initialize(); tpusnap needs the "
                "coordination service for cross-host snapshot "
                "consistency. Call jax.distributed.initialize() at "
                "startup or pass an explicit `comm`."
            )
    return Communicator()


def _backend_initialized() -> Optional[bool]:
    """Whether some XLA backend is already live in this process, checked
    without triggering initialization. Returns None when the private probe
    is unavailable (jax._src.xla_bridge moved): the caller must then fall
    back to the loud public-API check instead of assuming single-process —
    a silent False here is exactly the multi-host snapshot-collision mode
    this module is designed to fail loudly on."""
    try:
        from jax._src import xla_bridge as _xb
    except Exception:
        logger.warning(
            "tpusnap cannot probe jax._src.xla_bridge on this JAX version; "
            "falling back to jax.process_count() to rule out an "
            "uncoordinated multi-host job (this may initialize the device "
            "backend)."
        )
        return None
    try:
        return bool(getattr(_xb, "_backends", None))
    except Exception:
        return None
