"""Local/posix filesystem storage plugin.

Counterpart of /root/reference/torchsnapshot/storage_plugins/fs.py:26-49:
aiofiles-backed async I/O, a mkdir cache so each directory is created once,
and ranged reads by seek. Additionally uses the native helper
(tpusnap._native) for large GIL-released positional writes when available —
the reference leans on torch's native file I/O for the same effect.
"""

import asyncio
import io
import os
import pathlib
import threading
import time
import weakref
from concurrent.futures import ThreadPoolExecutor
from contextlib import contextmanager
from typing import Dict, List, Optional, Set, Tuple

try:
    import aiofiles
except ModuleNotFoundError:  # gated dep: fall back to thread-pool I/O
    aiofiles = None
import numpy as np

from .. import telemetry
from ..io_types import ReadIO, StoragePlugin, WriteIO, start_all_workers
from ..memoryview_stream import MemoryviewStream

# Buffers >= this go through the thread-pool native writer; small writes
# stay on the aiofiles path where syscall overhead doesn't matter.
_NATIVE_WRITE_THRESHOLD = 4 * 1024 * 1024

# Scratch buffers that one burst of reads may have out before a reader
# waits for one to come back instead of touching a fresh one, and that the
# pool keeps free at the most. Half the reader threads' count: of 2, 4 and
# 8 on the machine whose page faults set a restore's pace, 4 restored the
# benchmark's 3.65 GB in 1.4 s where 8 took 2.0 s and every blob its own
# buffer 3.0 s (PERF.md 6, PR 47); fewer first touches, and still enough
# buffers for a consumer to hold some while the readers fill the others.
_SCRATCH_BUFFERS = 4
# A reader waits only where the first touch of a fresh buffer has taken at
# least this share of its fresh reads' time in this burst: below it the
# storage sets the pace, and a stream that waits is a stream lost.
_SCRATCH_WAIT_TOUCH_SHARE = 0.25


class _Scratch:
    """One allocation of the pool: ``buf`` (4096-aligned uint8) and how many
    of its leading bytes a read has landed in before (``warm``)."""

    __slots__ = ("buf", "warm", "burst")

    def __init__(self, buf: np.ndarray, burst: int) -> None:
        self.buf = buf
        self.warm = 0
        self.burst = burst


class _ScratchPool:
    """The scratch buffers of one burst of reads of one plug-in instance.

    A fresh buffer's pages are zeroed and mapped one by one before a read
    can land in them, and on a kernel that does so under one lock for the
    whole process that first touch, not the storage, sets a restore's pace
    (PERF.md 6, PRs 44 and 47). A buffer that a read of this burst has
    landed in before takes the next read without it. So a read takes the
    smallest free buffer that holds it before it allocates one, and a
    buffer comes back when nothing refers any longer to what was handed
    out: ``view()`` hands out an array over the buffer's first bytes and
    the pool learns of that array's death (``weakref.finalize``); the
    memoryview a consumer is given, and whatever it builds over it, keep
    that array alive, a ``device_put`` in flight among them. No caller
    releases anything, and a buffer still referred to is never handed out
    again.

    While ``_SCRATCH_BUFFERS`` are out and none that fits is free, a reader
    waits for one to come back before it allocates, where the plug-in's
    own timings of this burst say the first touch is what a fresh read
    pays for (``_SCRATCH_WAIT_TOUCH_SHARE``), and never longer than a fresh
    read of that size has taken in this burst (counted from the first such
    read's end, for a reader that came before it; until then it stays only
    while the reads under way have not yet read for three times their
    touch). After a wait that ran out no reader begins one until something
    has come back: a backend that keeps the host memory for the restored
    array's life returns nothing, and costs each reader thread one wait.

    A burst lasts while a read of the plug-in is in flight (``reading()``,
    entered where the read is dispatched and again on the thread that runs
    it). When the last one leaves, the pool forgets its buffers and its
    timings; one that comes back later is dropped. A restore that starts
    finds the pool empty, as a restarted job does.

    Every method may be re-entered on its own thread: a view can die, and
    its finalizer run, wherever the collector runs. The lock is re-entrant
    and the sections below keep their state whole at every call."""

    def __init__(self, limit: int = _SCRATCH_BUFFERS) -> None:
        self._limit = limit
        self._cond = threading.Condition()  # over an RLock
        self._burst = 0
        self._in_flight = 0
        self._reset()

    def _reset(self) -> None:
        self._free: List[_Scratch] = []
        self._out = 0  # handed out in this burst and still referred to
        self._came_back = 0
        self._dry_at: Optional[int] = None  # `_came_back` when a wait last ran out
        self._landing = 0  # reads of this burst between take() and landed()
        # Fresh reads past their touch: id(entry) -> (touch seconds, when
        # the read began).
        self._reading: Dict[int, Tuple[float, float]] = {}
        self._fresh_bytes = 0  # of fresh reads that have ended, with their
        self._fresh_touch_s = 0.0  # touches' and
        self._fresh_read_s = 0.0  # reads' seconds

    @contextmanager
    def reading(self):
        with self._cond:
            self._in_flight += 1
        try:
            yield
        finally:
            with self._cond:
                self._in_flight -= 1
                if self._in_flight == 0:
                    self.clear()

    def clear(self) -> None:
        """The burst is over: what is free goes, what is out is forgotten."""
        with self._cond:
            self._burst += 1
            self._reset()
            self._cond.notify_all()

    def held_bytes(self) -> int:
        """Bytes of the buffers the pool keeps for a later read."""
        with self._cond:
            return sum(e.buf.nbytes for e in self._free)

    def _fresh_seconds(self, size: int) -> Optional[float]:
        """What a fresh read of ``size`` bytes has taken in this burst."""
        if not self._fresh_bytes:
            return None
        return (self._fresh_touch_s + self._fresh_read_s) * size / self._fresh_bytes

    def _touch_dominates(self) -> bool:
        """Whether the first touch is what this burst's fresh reads have
        paid for so far, those still reading among them."""
        now = time.monotonic()
        touch = self._fresh_touch_s + sum(t for t, _ in self._reading.values())
        read = self._fresh_read_s + sum(now - began for _, began in self._reading.values())
        return touch >= _SCRATCH_WAIT_TOUCH_SHARE * (touch + read)

    def take(self, size: int) -> _Scratch:
        """A buffer of ``size`` bytes or more for a read of this burst."""
        from .. import _native

        with self._cond:
            burst = self._burst
            began = time.monotonic()
            # Nothing has come back since a wait last ran out: a reader that
            # comes now does not begin one (those under way run their own
            # clocks out: one short read's bound is not the others').
            dry = self._dry_at == self._came_back
            while burst == self._burst:
                fits = [e for e in self._free if e.buf.nbytes >= size]
                if fits:
                    entry = min(fits, key=lambda e: e.buf.nbytes)
                    self._free.remove(entry)
                    break
                entry = None
                if self._out < self._limit or dry:
                    break
                bound = self._fresh_seconds(size)
                if bound is None:
                    # No fresh read of this burst has ended yet: the first
                    # that does says how long to wait, and the wait is
                    # counted from then. Until then a reader stays only
                    # while the reads under way may still say that the
                    # touch is what they pay for.
                    if not self._landing:
                        break
                    if self._reading and not self._touch_dominates():
                        break
                    self._cond.wait(min((t for t, _ in self._reading.values()), default=None))
                    began = time.monotonic()
                    continue
                if not self._touch_dominates():
                    break
                left = began + bound - time.monotonic()
                if left <= 0:
                    self._dry_at = self._came_back
                    break
                self._cond.wait(left)
            if burst == self._burst:
                self._out += 1
                self._landing += 1
        if entry is None:
            try:
                # 4096-aligned so the native direct read preads straight into
                # this buffer (zero-copy) instead of bouncing every chunk.
                entry = _Scratch(_native.aligned_empty(size), burst)
            except BaseException:
                with self._cond:
                    if burst == self._burst:
                        self._out -= 1
                        self._landing -= 1
                        self._cond.notify_all()
                raise
        return entry

    def touched(self, entry: _Scratch, touch_s: float) -> None:
        """``entry``, fresh, has been touched and its read begins."""
        with self._cond:
            if entry.burst == self._burst:
                self._reading[id(entry)] = (touch_s, time.monotonic())
                self._cond.notify_all()

    def landed(self, entry: _Scratch, size: int, touch_s: float, read_s: float) -> None:
        """The read into ``entry`` has ended, well or not; ``touch_s`` is
        nonzero where the buffer was fresh."""
        with self._cond:
            if entry.burst != self._burst:
                return
            self._landing -= 1
            self._reading.pop(id(entry), None)
            if touch_s:
                self._fresh_bytes += size
                self._fresh_touch_s += touch_s
                self._fresh_read_s += read_s
            self._cond.notify_all()

    def view(self, entry: _Scratch, size: int) -> np.ndarray:
        """The first ``size`` bytes of ``entry`` as an array whose death
        brings the buffer back. Hand out memoryviews of it, never slices
        of it: numpy gives a slice the allocation for its base, not this
        array."""
        out = entry.buf[:size]
        weakref.finalize(out, self._back, entry).atexit = False
        return out

    def _back(self, entry: _Scratch) -> None:
        with self._cond:
            if entry.burst != self._burst:
                return  # its burst is over: the memory goes with it
            self._out -= 1
            self._came_back += 1
            # The pool keeps as many as may be out before a reader waits,
            # the largest: what more comes back (after waits that ran out)
            # goes.
            self._free.append(entry)
            if len(self._free) > self._limit:
                self._free.remove(min(self._free, key=lambda e: e.buf.nbytes))
            self._cond.notify_all()


class FSStoragePlugin(StoragePlugin):
    supports_in_place_reads = True
    # Whole-op retry middleware (tpusnap.retry) wraps this plugin when it
    # is built from a URL: local filesystems rarely throw transient
    # errors, but network mounts (NFS/FUSE) and chaos runs do, and the
    # default errno/connection classifier covers both.
    wants_retry_middleware = True

    def in_place_read_overhead_bytes(self, nbytes: int) -> int:
        """Per-stream bounce memory of the native in-place read engine
        ((qd+1) x 8 MiB chunks, clamped to the read window — see
        ts_read_range_into_crc)."""
        from ..knobs import get_direct_io_qd

        qd = min(max(get_direct_io_qd(), 1), 8)  # native clamps identically
        return min(nbytes, (qd + 1) * 8 * 1024 * 1024)

    def __init__(self, root: str, storage_options=None) -> None:
        self.root = root
        self._dir_cache: Set[pathlib.Path] = set()
        self._executor: Optional[ThreadPoolExecutor] = None
        self._readers_of: Optional[ThreadPoolExecutor] = None  # all started
        self._a_reader_ran = False
        self._scratch = _ScratchPool()

    def _ensure_parent(self, path: pathlib.Path) -> None:
        parent = path.parent
        if parent not in self._dir_cache:
            parent.mkdir(parents=True, exist_ok=True)
            self._dir_cache.add(parent)

    def _get_executor(self) -> ThreadPoolExecutor:
        if self._executor is None:
            # 8 concurrent streams measurably out-run 4 on direct I/O
            # (deeper device queue); each stream is GIL-released in native
            # code so the extra threads cost nothing on the Python side.
            self._executor = ThreadPoolExecutor(
                max_workers=8, thread_name_prefix="tpusnap-fs"
            )
        return self._executor

    async def write(self, write_io: WriteIO) -> None:
        path = pathlib.Path(os.path.join(self.root, write_io.path))
        self._ensure_parent(path)
        buf = write_io.buf
        if len(buf) >= _NATIVE_WRITE_THRESHOLD or aiofiles is None:
            # One blocking write in a thread: releases the GIL for the whole
            # transfer and avoids aiofiles' per-chunk hop overhead. Also the
            # small-write path when aiofiles is not installed. The hand-off
            # records `write.queued` (the wait for one of the 8 threads)
            # and `write.work` (the write) on that thread, and
            # `write.resumed` (the loop's lateness afterwards) here.
            await telemetry.run_handoff(
                self._get_executor(), "write", _write_file, path, buf, bytes=len(buf)
            )
        else:
            async with aiofiles.open(path, "wb") as f:
                await f.write(buf)
        if _durable_commit():
            # Durable-commit mode: every blob's DATA must be on stable
            # storage before the metadata commit declares the snapshot
            # durable — fsync on the metadata file alone does not write
            # back other files' dirty pages (small blobs and fallback
            # engines go through the page cache). Dirent durability is
            # handled at commit time (write_atomic fsyncs every
            # directory this plugin created).
            # A second trip through the same executor: its queue wait is
            # one more `write.queued`, the fsync itself `write.fsync`,
            # the loop's lateness after it one more `write.resumed`.
            await telemetry.run_handoff(
                self._get_executor(), "write", _fsync_path, str(path), work="write.fsync"
            )

    async def write_atomic(self, write_io: WriteIO, durable: bool = False) -> None:
        """Temp-file + rename: a crash mid-write never destroys an
        existing file at the destination. With ``durable=True`` the temp
        file is fsync'd before the rename and the parent directory
        after, so a power loss after return can never leave the rename
        durable with the DATA not (an empty/torn ``.snapshot_metadata``)
        nor lose the commit. The fsync is caller-opted because its cost
        is NOT metadata-sized: an fsync right after a multi-GB take
        flushes the storage cache of everything just written (~2 s
        measured here) — callers rewriting already-committed metadata
        always opt in, the take commit does so via
        TPUSNAP_DURABLE_COMMIT (see io_types.write_atomic)."""
        path = pathlib.Path(os.path.join(self.root, write_io.path))
        self._ensure_parent(path)
        tmp = path.with_name(path.name + f".tmp.{os.getpid()}")
        loop = asyncio.get_running_loop()

        def work():
            try:
                _write_file(tmp, write_io.buf)
                if durable:
                    _fsync_path(str(tmp))
                os.replace(tmp, path)
                if durable:
                    # Every directory this plugin created, plus the
                    # commit's own parent: the dirents of the blobs
                    # written before this commit become durable with it.
                    for d in {str(p) for p in self._dir_cache} | {
                        str(path.parent)
                    }:
                        _fsync_path(d)
            except BaseException:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
                raise

        await loop.run_in_executor(self._get_executor(), work)

    async def read(self, read_io: ReadIO) -> None:
        path = os.path.join(self.root, read_io.path)
        # A whole-blob read chooses its path (in place, native, small) and
        # its length by the blob's size. Where the request brings the
        # length the manifest implies (`expected_nbytes`), this thread,
        # which runs the event loop and every other read's dispatch, asks
        # the filesystem nothing: the reader thread asks for the file's
        # real size before it reads, and reads a file of another length
        # exactly as if its size had been asked here.
        # `known`: None for a ranged read, else whether the length came
        # with the request.
        known = None
        if read_io.byte_range is not None:
            offset, end = read_io.byte_range
        elif read_io.expected_nbytes is not None:
            offset, end, known = 0, read_io.expected_nbytes, True
        else:
            offset, end, known = 0, os.path.getsize(path), False
        n = end - offset
        # Exact-size match only: a truncated blob (n = actual file size <
        # destination) must fall through to the generic path, whose
        # deserialize raises on the size mismatch even with checksums off.
        if read_io.into is not None and n == read_io.into.nbytes:
            try:
                await self._native_read_into(read_io, path, offset, n, known)
                return
            except _OtherLength as e:
                # Shorter or longer than the request said, and nothing
                # written: the generic path reads what is there.
                n, known = e.args[0], None
        if n >= _NATIVE_WRITE_THRESHOLD:
            read_io.buf = await self._native_read(path, offset, n, read_io, known)
            return
        # A small blob whose length came with the request is read to its
        # end, whatever its length: what asking for its size would read.
        upto = -1 if known else n
        if aiofiles is None:

            def work():
                with open(path, "rb") as f:
                    if offset:
                        f.seek(offset)
                    return f.read(upto)

            data = await telemetry.run_handoff(
                self._get_executor(), "read", work, bytes=n
            )
            read_io.buf = io.BytesIO(data)
            return
        async with aiofiles.open(path, "rb") as f:
            if offset:
                await f.seek(offset)
            read_io.buf = io.BytesIO(await f.read(upto))

    async def _to_reader(self, work, n: int, known: Optional[bool], spans: bool = True):
        """Hand ``work`` to a reader thread, tracked for
        ``drain_in_flight``; with ``spans`` False the body records its own
        spans in ``read.work``'s place. ``known`` says where a whole-blob read's
        length came from (None: a ranged read), which is counted here:
        c:``read.length_known`` (with the request: this thread made no
        filesystem call for the read) or c:``read.length_asked`` (this
        thread, the event loop's, asked the filesystem before the
        hand-off). Before the first hand-off all eight threads are
        started: one started by a later submit would be started on this
        thread beside the reads already under way (a plug-in that only
        writes starts its threads as its writes need them, as ever). The
        first body of this plug-in says how many of its threads were alive
        when it began (g:``fs.readers_at_first_read``, on the recorder of
        the operation that dispatched the read)."""
        if known is not None:
            telemetry.incr("read.length_known" if known else "read.length_asked")
        executor = self._get_executor()
        if self._readers_of is not executor:
            self._readers_of = executor
            start_all_workers(executor)
        rec = telemetry.current()

        def body():
            if not self._a_reader_ran:
                self._a_reader_ran = True
                if rec is not None:
                    alive = sum(t.is_alive() for t in list(executor._threads))
                    rec.gauge_max("fs.readers_at_first_read", float(alive))
            return work()

        return await telemetry.run_handoff(
            executor, "read", body, work=spans, submit=self._submit_tracked, bytes=n
        )

    async def _native_read_into(
        self, read_io: ReadIO, path: str, offset: int, n: int, known: Optional[bool] = None
    ) -> None:
        """In-place read: bytes land directly in the consumer-provided
        destination (the restore target's memory) with the checksum fused
        into the native copy-out — no scratch buffer, no separate verify
        pass, no deserialize+copy pass in the consume stage. Where ``n``
        came with the request (``known``) and the file is another length,
        raises ``_OtherLength`` with nothing read."""
        dst = read_io.into

        def work():
            from .. import _native

            if known:
                real = os.path.getsize(path)
                if real != n:
                    raise _OtherLength(real)
            return _native.read_range_into(
                path, offset, n, dst, want_crc=read_io.want_crc
            )

        got, crc, algo = await self._to_reader(work, n, known)
        if got != n:
            raise IOError(
                f"short read: got {got} of {n} bytes at offset {offset} "
                f"from {path} — the snapshot blob is truncated"
            )
        read_io.in_place = True
        read_io.crc32c = crc
        read_io.crc_algo = algo
        read_io.buf = MemoryviewStream(dst[:n])

    async def _native_read(
        self, path: str, offset: int, n: int, read_io=None, known: Optional[bool] = None
    ):
        """Single GIL-released pread in a thread (native helper), landing
        in a scratch buffer of this burst's pool (``_ScratchPool``): one a
        read of the burst has landed in before where one is free, or comes
        back within the pool's bounded wait, else an *uninitialized* one
        allocated on the worker thread (large np.empty calls contend on
        the process's mmap lock under concurrent read page-fault traffic
        and would stall the event loop for tens of ms each). The reader
        records its wait for the buffer (``read.scratch_wait``) and then
        ``read.work`` (touch, read, hash) itself, both children of the
        request's span as ``read.queued`` is; c:``read.scratch_fresh_bytes``
        counts the bytes touched for the first time, and
        c:``read.scratch_reused_bytes`` those read into memory that a read
        had landed in before.

        When the request asks for a checksum (``want_crc``) it is
        computed here on the read thread — overlapping other streams'
        I/O — so the consume stage verifies a 4-byte value instead of
        re-reading the buffer (sharded-shard reads use this; dense numpy
        targets go further via the in-place ``into`` path).

        Where ``n`` came with the request (``known``), the read's length
        is the file's real size, asked here on the reader thread: a blob
        shorter or longer than the manifest implies is delivered as it
        is, and the consumer's deserialize raises on it as it always
        has. The consumer is given a memoryview of exactly the bytes
        read: a longer buffer's tail is never exposed."""
        want_crc = read_io is not None and read_io.want_crc
        pool = self._scratch
        rec = telemetry.current()

        def work():
            # A read of the burst in its own right: the body outlives a
            # request that was cancelled.
            with pool.reading():
                return read()

        def read():
            from .. import _native

            size = os.path.getsize(path) if known else n
            entry = None
            touch_s = read_s = 0.0
            try:
                with telemetry.span("read.scratch_wait", kind=telemetry.WAIT, bytes=size):
                    entry = pool.take(size)
                with telemetry.span("read.work", bytes=size):
                    arr = pool.view(entry, size)
                    began = time.monotonic()
                    if entry.warm < size:
                        # The first touch of what no read has landed in,
                        # here and not inside the read, whose faults on a
                        # sandboxed kernel hold up every other thread of
                        # the restore (see ts_touch_pages).
                        _native.touch_pages(arr[entry.warm :])
                        touch_s = time.monotonic() - began
                        pool.touched(entry, touch_s)
                    telemetry.incr("read.scratch_fresh_bytes", size - min(entry.warm, size), rec=rec)
                    telemetry.incr("read.scratch_reused_bytes", min(entry.warm, size), rec=rec)
                    crc = algo = None
                    if want_crc:
                        got, crc, algo = _native.read_range_into(
                            path, offset, size, arr, want_crc=True
                        )
                    else:
                        got = _read_range(path, offset, size, arr.data)
                    entry.warm = max(entry.warm, got)
                    read_s = time.monotonic() - began - touch_s
                    return memoryview(arr)[:got], size, got, crc, algo
            finally:
                if entry is not None:  # whatever was raised: a reader may wait for this
                    pool.landed(entry, size, touch_s, read_s)

        with pool.reading():
            view, n, got, crc, algo = await self._to_reader(
                work, n, known, spans=False
            )
        if want_crc and got == n:
            read_io.crc32c = crc
            read_io.crc_algo = algo
        return MemoryviewStream(view)

    async def delete(self, path: str) -> None:
        full = os.path.join(self.root, path)
        loop = asyncio.get_running_loop()
        await loop.run_in_executor(None, os.remove, full)

    async def list_with_sizes(self) -> Optional[dict]:
        """``{relative_path: size}`` for every regular file under the
        root (lifecycle tooling: fsck orphan enumeration, gc). Missing
        root → empty dict (an un-taken snapshot path is simply empty)."""
        loop = asyncio.get_running_loop()

        def work():
            out = {}
            root = os.path.abspath(self.root)
            if not os.path.isdir(root):
                return out
            for dirpath, _dirnames, filenames in os.walk(root):
                for name in filenames:
                    full = os.path.join(dirpath, name)
                    rel = os.path.relpath(full, root).replace(os.sep, "/")
                    try:
                        out[rel] = os.path.getsize(full)
                    except OSError:
                        continue  # racing deletion (concurrent gc/abort)
            return out

        return await loop.run_in_executor(self._get_executor(), work)

    async def flush_created_dirs(self) -> None:
        """fsync every directory this instance created (durable-commit
        mode: each rank runs this after its writes drain, so dirents of
        all ranks' blobs are stable before rank 0 commits)."""
        dirs = {str(p) for p in self._dir_cache} | {self.root}
        loop = asyncio.get_running_loop()

        def work():
            for d in dirs:
                try:
                    _fsync_path(d)
                except OSError:
                    pass  # deleted/renamed since creation

        await loop.run_in_executor(self._get_executor(), work)

    async def close(self) -> None:
        self._scratch.clear()
        if self._executor is not None:
            from ..io_types import shutdown_plugin_executor

            shutdown_plugin_executor(self._executor)
            self._executor = None


def _durable_commit() -> bool:
    from ..knobs import is_durable_commit_enabled

    return is_durable_commit_enabled()


class _OtherLength(Exception):
    """The file is not the length that came with the read's request; its
    one argument is the length it has."""


def _fsync_path(path: str) -> None:
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def _write_file(path: pathlib.Path, buf) -> None:
    from .. import _native as native

    if native.available():
        native.write_file(str(path), buf)
        return
    native._write_all(str(path), memoryview(buf).cast("B"))


def _read_range(path: str, offset: int, n: int, out: bytearray) -> int:
    from .. import _native as native

    return native.read_range(path, offset, n, out)
