"""Core I/O abstractions: write/read requests, stagers/consumers, and the
StoragePlugin ABC.

TPU-native counterpart of /root/reference/torchsnapshot/io_types.py:
same pipeline roles —

- ``WriteReq``  = logical path + ``BufferStager`` (produces bytes, e.g. by
  device→host DMA + zero-copy serialization).
- ``ReadReq``   = logical path + optional byte range + ``BufferConsumer``
  (deserializes into the restore target in place).
- ``WriteIO``/``ReadIO`` = the physical request handed to a storage plugin.
- ``StoragePlugin`` = async write/read/delete/close + sync shims.

Staging/consuming cost models drive the scheduler's memory budget
(reference io_types.py:30-72).
"""

from __future__ import annotations

import abc
import asyncio
import contextlib as _contextlib
import io
import threading as _threading
from concurrent.futures import Executor, ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Generic, List, Optional, Tuple, TypeVar, Union

BufferType = Union[bytes, bytearray, memoryview]

# The snapshot-internal sidecar namespace: telemetry traces, progress
# heartbeats, journal records, roofline probe streams. The ONE
# definition of the namespace root, shared by the layers that exempt
# whole-namespace traffic — journaling and histogram sampling — so it
# cannot silently drift apart. (fsck classifies per FAMILY under this
# root: lifecycle._is_legit_sidecar and the empty/foreign exemptions
# name specific subdirectories, deliberately narrower than the root.)
SIDECAR_PREFIX = ".tpusnap/"

# Canonical sidecar paths under the namespace root. Every layer that
# writes or classifies sidecar traffic imports these — hardcoding the
# string anywhere else is a lint violation (TPS003): a namespace that
# exists in five private copies is five chances for fsck's
# classification and the writers to drift apart.
JOURNAL_PATH = SIDECAR_PREFIX + "journal"  # rank 0's take marker
JOURNAL_RECORDS_DIR = SIDECAR_PREFIX + "journal.d"  # per-rank evidence
PROGRESS_DIR = SIDECAR_PREFIX + "progress"  # heartbeat records
TELEMETRY_DIR = SIDECAR_PREFIX + "telemetry"  # per-rank Chrome traces
PROBE_DIR = SIDECAR_PREFIX + "probe"  # roofline probe streams
FLIGHT_DIR = SIDECAR_PREFIX + "flight"  # flight-recorder event logs
# Write-back tiering (tpusnap.tiering): the crash-safe upload journal a
# tiered take keeps in its LOCAL tier — per-blob CRC32C+XXH64 evidence
# of what has been proven remote, plus the durability state marker
# (state "pending" = local-committed, "durable" = remote-durable).
UPLOAD_JOURNAL_PATH = SIDECAR_PREFIX + "upload_journal"
# Content-addressed store (tpusnap.cas): per-rank ref record files a
# CAS-composed snapshot keeps instead of private payload copies — each
# entry maps a manifest location to the (nbytes, CRC32C, XXH64) triple
# that keys the shared blob. The refs ARE the store's gc liveness
# roots, so they are journaled like PR 3 evidence (atomic per-rank
# rewrites) and flushed strictly before the metadata commit.
CAS_REFS_DIR = SIDECAR_PREFIX + "cas_refs"  # per-rank ref records

T = TypeVar("T")


class Future(Generic[T]):
    """Tiny completion cell for values materialized during read execution
    (reference io_preparer returns ``Future`` for inflated objects)."""

    def __init__(self, obj: Optional[T] = None) -> None:
        self.obj = obj


@dataclass
class WriteIO:
    path: str
    buf: BufferType


@dataclass
class ReadIO:
    path: str
    byte_range: Optional[Tuple[int, int]] = None
    buf: io.BytesIO = field(default_factory=io.BytesIO)
    # In-place read support: when ``into`` is set, a capable plugin may
    # land the bytes directly in this writable buffer (the restore
    # target's own memory) instead of allocating a scratch buffer, and
    # set ``in_place=True``. With ``want_crc``, the plugin also reports
    # the checksum of the bytes it delivered (computed inside the native
    # read, fused with the copy-out) via ``crc32c``/``crc_algo`` so the
    # consumer verifies a 4-byte value instead of re-hashing gigabytes.
    # Plugins without in-place support simply ignore these fields.
    into: Optional[memoryview] = None
    want_crc: bool = False
    in_place: bool = False
    crc32c: Optional[int] = None
    crc_algo: Optional[str] = None
    # Access-ledger provenance: plugins that redirect the read away from
    # the plain local path stamp where the bytes actually came from
    # ("cas" for a ref-translated store read, "evicted-read-through" for
    # a tiered local miss served by the remote). Left None for ordinary
    # reads; the scheduler's recorder then attributes the read to the
    # ambient storage tier (local/remote).
    source: Optional[str] = None
    # The length of a whole-blob read (``byte_range`` None) as the
    # manifest implies it, where the request knows it. A plug-in that
    # would otherwise ask the backend for the object's size on the event
    # loop's thread, to choose a path and a length, takes this instead and
    # checks the real size where it reads (the fs plug-in: on the reader
    # thread); a blob of another length fails as it does when the size is
    # asked. None: the plug-in asks. Middlewares that copy a ReadIO per
    # attempt carry it.
    expected_nbytes: Optional[int] = None

    def as_new_request(self, path: Optional[str] = None) -> "ReadIO":
        """What this ReadIO asks for (of ``path``, where a middleware reads
        the same bytes from elsewhere) and none of what a read has filled
        in: the copy a middleware makes per attempt or per tier. Every
        request field goes through here, so that none is dropped on the
        way to the plug-in."""
        return ReadIO(
            path=self.path if path is None else path,
            byte_range=self.byte_range,
            into=self.into,
            want_crc=self.want_crc,
            expected_nbytes=self.expected_nbytes,
        )


class _SkipWrite:
    """Sentinel a stager may return instead of bytes: the blob's content
    is already persisted (incremental snapshot dedup — the stager
    rewrote its entry to reference the previous snapshot's blob), so the
    pipeline completes this request without any storage I/O."""

    __slots__ = ()

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return "SKIP_WRITE"


SKIP_WRITE = _SkipWrite()


class BufferStager(abc.ABC):
    @abc.abstractmethod
    async def stage_buffer(self, executor: Optional[Executor] = None) -> BufferType:
        """Produce the bytes to persist (may run DtoH copies in
        ``executor``), or ``SKIP_WRITE`` when the content is already
        persisted and this request needs no storage I/O."""

    @abc.abstractmethod
    def get_staging_cost_bytes(self) -> int:
        """Peak host memory consumed while this buffer is staged."""

    def get_planned_bytes(self) -> int:
        """Payload bytes this request will actually stage/write — the
        progress denominator. Defaults to the staging cost; stagers
        whose cost model charges MORE than the payload (async array
        clones hold a second host copy, so their cost is 2x) override
        this so heartbeat percentages can reach 100."""
        return self.get_staging_cost_bytes()

    def start_dtoh(self) -> int:
        """Start whatever device-to-host copy this request's staging
        will wait for, once, and return the bytes under way. The write
        scheduler calls it a fixed depth ahead of the request it
        dispatches; the default has no copy to start."""
        return 0

    def aliases_caller_memory(self) -> bool:
        """Whether the bytes this request stages may live in memory the
        caller can write IN PLACE once ``async_take`` has returned (a
        numpy leaf, a ``pinned_host`` or CPU-backend array). Such a
        request counts towards the blocked window of a pipelined async
        take; one that answers False (an accelerator-resident array,
        held by reference: it can only be donated, which deletes it and
        fails the take loudly) is staged by the background drain. The
        default is the safe answer: it counts."""
        return True

    def stages_callers_host_value(self) -> bool:
        """Whether what this request stages is the host value that the
        runtime keeps on the caller's own array (an accelerator leaf
        that crosses as it lies and is written as it landed): memory
        the caller holds until it deletes the array, of which the end
        of the write gives nothing back. The write scheduler charges
        its staging budget what a write's end frees, so it asks at the
        dispatch (the leaf's copy has been started by then) and again
        when the request is staged; every buffer of tpusnap's own (a
        clone, a slab, a turned or compressed blob, the host value of
        an owned copy) answers False, which is the default: charged."""
        return False


def stager_aliases_caller_memory(stager: BufferStager) -> bool:
    """``stager.aliases_caller_memory()``; a stager that is no
    ``BufferStager`` and has no such method counts as aliasing."""
    ask = getattr(stager, "aliases_caller_memory", None)
    return True if ask is None else bool(ask())


def stager_start_dtoh(stager: BufferStager, beside_steps: bool = True) -> int:
    """``stager.start_dtoh()``; a stager that is no ``BufferStager`` and
    has no such method has no copy to start. ``beside_steps``: whether
    the caller's steps may run while the bytes cross; a stager that
    would do something for their sake (``ArrayBufferStager.beside_steps``)
    is told where they cannot."""
    start = getattr(stager, "start_dtoh", None)
    if start is None:
        return 0
    if not beside_steps and hasattr(stager, "beside_steps"):
        stager.beside_steps = False
    return int(start())


def stager_stages_callers_host_value(stager: BufferStager) -> bool:
    """``stager.stages_callers_host_value()``; a stager that has no such
    method stages memory of its own, and is charged."""
    ask = getattr(stager, "stages_callers_host_value", None)
    return False if ask is None else bool(ask())


def stager_went_cow(stager: BufferStager) -> bool:
    """Whether ``stager`` staged the caller's live bytes under
    copy-on-write: itself (``cow_pending``: the buffer it returned is
    the live memory, verified after its write) or, a slab, for a member
    it took so (``took_cow_members``). A take with such a stager lets a
    caller mutate or donate its state only once this rank's writes have
    drained (``PendingIOWork.staged``)."""
    return bool(
        getattr(stager, "cow_pending", False)
        or getattr(stager, "took_cow_members", False)
    )


@dataclass
class WriteReq:
    path: str
    buffer_stager: BufferStager


class BufferConsumer(abc.ABC):
    @abc.abstractmethod
    async def consume_buffer(
        self, buf: BufferType, executor: Optional[Executor] = None
    ) -> None:
        """Deserialize ``buf`` into the restore target."""

    @abc.abstractmethod
    def get_consuming_cost_bytes(self) -> int:
        """Peak host memory consumed while this buffer is being consumed."""

    async def consume_read_io(
        self, read_io: ReadIO, executor: Optional[Executor] = None
    ) -> None:
        """Consume a completed ReadIO. The default path hands the read
        buffer to ``consume_buffer``; consumers whose reads may land
        in place override this to skip the deserialize+copy pass when
        ``read_io.in_place`` is set."""
        await self.consume_buffer(read_io.buf.getbuffer(), executor)


@dataclass
class ReadReq:
    path: str
    buffer_consumer: BufferConsumer
    byte_range: Optional[Tuple[int, int]] = None
    # Writable destination for plugins that support in-place reads (the
    # restore target's memory when the consumer knows landing there is
    # correct); see ReadIO.into. ``want_crc`` requests the fused
    # read-time checksum of the delivered bytes.
    into: Optional[memoryview] = None
    want_crc: bool = False
    # The blob's length as the manifest implies it, for a whole-blob read
    # (``byte_range`` None) whose preparer knows it; see
    # ReadIO.expected_nbytes.
    expected_nbytes: Optional[int] = None
    # Access-ledger attribution: the MANIFEST path this physical read
    # serves ("<rank>/<logical_path>" — the storage ``path`` is a blob
    # location, shared across leaves and meaningless to a reader).
    # Empty string = unattributed (manifest/metadata traffic).
    logical_path: str = ""
    # When the batcher merges several byte-ranged requests on one
    # location into a single spanning read, per-member attribution
    # survives here: [(logical_path, start, end), ...] in storage-blob
    # coordinates. None = the read serves exactly ``logical_path``
    # over ``byte_range``.
    access_parts: Optional[List[Tuple[str, int, int]]] = None


class StoragePlugin(abc.ABC):
    """Storage backend. Implementations must be safe for many concurrent
    coroutines (the scheduler keeps up to 16 requests in flight)."""

    # Plugins that honor ReadIO.into (bytes land in the consumer-provided
    # destination) set this True; the scheduler then charges such reads
    # only the plugin's transient overhead instead of the blob size.
    supports_in_place_reads: bool = False

    # Middleware markers consulted by the scheme registry
    # (storage_plugin.url_to_storage_plugin): ``wants_retry_middleware``
    # opts the plugin into the unified whole-op retry wrapper
    # (tpusnap.retry); ``handles_own_retries`` marks plugins with
    # internal, finer-grained retry logic (gcs retries per chunk) that
    # must not be double-wrapped.
    wants_retry_middleware: bool = False
    handles_own_retries: bool = False

    def classify_transient(self, exc: BaseException) -> bool:
        """Whether ``exc`` from this backend is worth retrying. The
        retry middleware consults this; plugins override to recognize
        backend-specific throttle/timeout shapes."""
        from .retry import default_classify_transient

        return default_classify_transient(exc)

    def in_place_read_overhead_bytes(self, nbytes: int) -> int:
        """Peak transient scratch memory an in-place read of ``nbytes``
        allocates inside this plugin (drives the scheduler's consuming
        budget). The conservative default assumes a full-size buffer;
        plugins that stream into the destination override with their
        actual bounce/chunk footprint."""
        return nbytes

    def _submit_tracked(self, executor, fn):
        """Run ``fn`` on ``executor``, tracked for ``drain_in_flight``.
        Plugins route any thread-offloaded work that writes into
        caller-owned buffers through this."""
        inflight = self.__dict__.setdefault("_tracked_inflight", set())
        future = executor.submit(fn)
        inflight.add(future)
        future.add_done_callback(inflight.discard)
        return asyncio.wrap_future(future)

    def drain_in_flight(self) -> None:
        """Block until worker-thread I/O this plugin offloaded via
        ``_submit_tracked`` has finished. Cancelling an asyncio task
        does NOT interrupt its executor work — after an aborted read, a
        plugin thread may still be writing into a caller-owned in-place
        destination. The scheduler's abort path calls this before
        re-raising so no stale write races the caller's error
        handling."""
        import concurrent.futures

        pending = list(self.__dict__.get("_tracked_inflight", ()))
        if pending:
            concurrent.futures.wait(pending)

    @abc.abstractmethod
    async def write(self, write_io: WriteIO) -> None: ...

    async def write_atomic(self, write_io: WriteIO, durable: bool = False) -> None:
        """Write that either fully lands or leaves any existing object
        untouched. Object stores are per-PUT atomic already, so the
        default delegates to ``write``; filesystem plugins override with
        temp-file + rename (a plain truncate-then-write would destroy a
        previously valid file on a mid-write crash — this matters when
        REWRITING committed metadata, e.g. ``materialize``).

        ``durable=True`` additionally makes the committed object survive
        POWER LOSS before returning (fs: fsync the temp file, rename,
        then fsync every directory the plugin created — so blob dirents
        written before the commit become durable with it; object stores
        are durable per PUT already). Callers rewriting
        already-committed metadata pass True (cheap there and the
        downside is destroying good state); the take commit passes the
        TPUSNAP_DURABLE_COMMIT knob, which ALSO fsyncs each blob file
        at write time — fsyncs right after a multi-GB take force a
        storage-cache flush of everything just written (~seconds), a
        cost the baselines it is benchmarked against (torch.save, the
        reference) never pay."""
        await self.write(write_io)

    @abc.abstractmethod
    async def read(self, read_io: ReadIO) -> None: ...

    @abc.abstractmethod
    async def delete(self, path: str) -> None: ...

    async def list_with_sizes(self) -> Optional[dict]:
        """Enumerate every object under this plugin's root as
        ``{relative_path: size_bytes}``, or ``None`` when the backend
        cannot list (the default). Powers offline lifecycle tooling —
        ``fsck``'s orphan-blob enumeration and ``gc``'s reclamation —
        which degrade gracefully (no orphan scan) on backends without
        it. Filesystem plugins implement it with a directory walk."""
        return None

    def sync_list_with_sizes(
        self, event_loop: Optional[asyncio.AbstractEventLoop] = None
    ) -> Optional[dict]:
        return _run(self.list_with_sizes(), event_loop)

    async def flush_created_dirs(self) -> None:
        """Make the dirents of everything this plugin instance created
        durable (fs: fsync each created directory). Called by EVERY rank
        after its writes drain, before the commit barrier, when
        TPUSNAP_DURABLE_COMMIT is on — the committing rank's
        ``write_atomic(durable=True)`` can only fsync its OWN
        directories, not the ones other ranks' plugin instances made.
        Default no-op (object stores have no dirents)."""
        return None

    def sync_flush_created_dirs(
        self, event_loop: Optional[asyncio.AbstractEventLoop] = None
    ) -> None:
        _run(self.flush_created_dirs(), event_loop)

    async def close(self) -> None:  # optional override
        return None

    # Sync shims (reference io_types.py:96-111): convenience wrappers used
    # outside the scheduler's event loop (metadata read/write).
    def sync_write(
        self, write_io: WriteIO, event_loop: Optional[asyncio.AbstractEventLoop] = None
    ) -> None:
        _run(self.write(write_io), event_loop)

    def sync_write_atomic(
        self,
        write_io: WriteIO,
        event_loop: Optional[asyncio.AbstractEventLoop] = None,
        durable: bool = False,
    ) -> None:
        _run(self.write_atomic(write_io, durable=durable), event_loop)

    def sync_read(
        self, read_io: ReadIO, event_loop: Optional[asyncio.AbstractEventLoop] = None
    ) -> None:
        _run(self.read(read_io), event_loop)

    def sync_delete(
        self, path: str, event_loop: Optional[asyncio.AbstractEventLoop] = None
    ) -> None:
        _run(self.delete(path), event_loop)

    def sync_close(
        self, event_loop: Optional[asyncio.AbstractEventLoop] = None
    ) -> None:
        _run(self.close(), event_loop)


# --- finalizer-safe close -------------------------------------------------
#
# Joining a thread from a GC finalizer can deadlock the process: if the
# collection that runs ``Snapshot.__del__`` fires inside a STARTING
# thread's ``Thread._set_tstate_lock`` (which holds
# ``threading._shutdown_locks_lock``), the join's ``Thread._stop``
# re-acquires that same lock and the thread waits on itself forever
# (observed killing a tier-1 run). Explicit closes KEEP joining — the
# take-abort path relies on close as its quiescence point for in-flight
# I/O threads (a straggler write surviving close could recreate a
# just-deleted blob of an aborted take). Only the finalizer path opts
# out, via this thread-local guard consulted by the executor-owning
# plugins' ``close()``.

_finalizer_close = _threading.local()


@_contextlib.contextmanager
def finalizer_close_scope():
    """Mark plugin ``close()`` calls on this thread as GC-finalizer
    driven: executor shutdowns skip their thread joins (queued work
    still runs; the interpreter joins workers at exit)."""
    # Save/restore (not set/clear): a nested finalizer — close()
    # dropping the last reference to another Snapshot — must not
    # re-enable joins for the OUTER finalizer still unwinding.
    prior = getattr(_finalizer_close, "active", False)
    _finalizer_close.active = True
    try:
        yield
    finally:
        _finalizer_close.active = prior


def close_may_join() -> bool:
    """Whether a plugin ``close()`` may join threads (False only inside
    :func:`finalizer_close_scope`)."""
    return not getattr(_finalizer_close, "active", False)


def start_all_workers(executor: ThreadPoolExecutor) -> None:
    """Start every thread ``executor`` may have, now, so that no later
    ``submit`` starts one.

    The stdlib pool starts a thread inside ``submit`` whenever none is
    idle, and ``Thread.start()`` waits for the new thread to come up: on
    the thread that runs an event loop that is a blocking call a request,
    paid beside the reads already running (on the benchmark's host 0.4 ms
    alone, 63 ms beside two or three reads, 0.7 s beside eight: PERF.md 6,
    PR 44), which handed a restore's reads to their readers one by one
    and kept every finished read from its consumer meanwhile. Here the
    starts happen together, before the first
    body runs: ``max_workers`` bodies that wait on ``go`` need as many
    threads, so each submit that finds no idle worker starts one;
    afterwards every submit is a queue put."""
    go = _threading.Event()
    try:
        for _ in range(executor._max_workers):
            executor.submit(go.wait)
    finally:
        go.set()


def shutdown_plugin_executor(executor) -> None:
    """The one place the join-on-close policy lives: explicit closes
    JOIN (abort-path quiescence — a straggler write thread surviving
    close could recreate a just-deleted blob of an aborted take);
    GC-finalizer closes must NOT (see the deadlock note above) — and
    must not even WAIT on the executor's shutdown lock:
    ``ThreadPoolExecutor.shutdown`` blocks on ``_shutdown_lock``, while
    ``submit`` holds its own ``_shutdown_lock`` and then the module's
    ``_global_shutdown_lock``. GC can fire this finalizer on a thread
    that is inside executor B's ``submit`` (holding the global lock)
    while another thread is inside executor A's ``submit`` (holding
    A's lock, waiting for the global one) — a blocking shutdown of A
    here completes the AB/BA deadlock. The runtime lock-order watchdog
    (tpusnap.devtools.lockwatch) caught exactly this interleaving in a
    tier-1 run. So the finalizer path replicates
    ``shutdown(wait=False)``'s body under a TRYLOCK and simply leaves
    the executor to the interpreter's exit reaper when the lock is
    contended (or the stdlib internals have moved).
    Executor-owning plugins call this from ``close()``."""
    if close_may_join():
        executor.shutdown(wait=True)
        return
    lock = getattr(executor, "_shutdown_lock", None)
    try:
        if lock is None or not lock.acquire(False):
            return
        try:
            executor._shutdown = True
            # Wake idle workers blocked in _work_queue.get so they exit
            # instead of parking until interpreter shutdown.
            executor._work_queue.put(None)
        finally:
            lock.release()
    except Exception:
        # Unknown executor shape: taking no lock beats taking a risk —
        # the interpreter joins surviving workers at exit.
        return


def run_on_loop(event_loop: asyncio.AbstractEventLoop, coro):
    """``run_until_complete`` that cannot strand tasks on the loop.

    A BaseException delivered inside the loop machinery (Ctrl-C between
    callbacks) escapes ``run_until_complete`` without unwinding the
    top-level coroutine; on a per-call loop the subsequent close()
    destroyed the orphan, but on a REUSED loop (cached Snapshot
    resources) the next ``run_until_complete`` would resume it —
    writing into the previous call's buffers. Cancel and drain the
    top-level task before re-raising."""
    from . import telemetry  # imports this module

    telemetry.note_loop_thread()  # a watched operation samples this thread
    task = event_loop.create_task(coro) if asyncio.iscoroutine(coro) else coro
    try:
        return event_loop.run_until_complete(task)
    except BaseException:
        task.cancel()
        try:
            event_loop.run_until_complete(task)
        except BaseException:
            pass
        raise


def _run(coro, event_loop: Optional[asyncio.AbstractEventLoop]):
    if event_loop is not None:
        return run_on_loop(event_loop, coro)
    return asyncio.run(coro)


def read_io_bytes(read_io: ReadIO) -> memoryview:
    """The bytes a plugin filled into a ReadIO."""
    return read_io.buf.getbuffer()


def total_write_bytes(write_ios: List[WriteIO]) -> int:
    return sum(len(w.buf) for w in write_ios)
