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
from concurrent.futures import ThreadPoolExecutor
from typing import Optional, Set

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

    async def _to_reader(self, work, n: int, known: Optional[bool]):
        """Hand ``work`` to a reader thread, tracked for
        ``drain_in_flight``. ``known`` says where a whole-blob read's
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
            executor, "read", body, submit=self._submit_tracked, bytes=n
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
        in an *uninitialized* numpy buffer — preallocating via BytesIO
        would zero-fill n bytes first. The allocation itself also happens
        on the worker thread: large np.empty calls contend on the
        process's mmap lock under concurrent read page-fault traffic and
        would stall the event loop for tens of ms each.

        When the request asks for a checksum (``want_crc``) it is
        computed here on the read thread — overlapping other streams'
        I/O — so the consume stage verifies a 4-byte value instead of
        re-reading the buffer (sharded-shard reads use this; dense numpy
        targets go further via the in-place ``into`` path).

        Where ``n`` came with the request (``known``), the read's length
        is the file's real size, asked here on the reader thread: a blob
        shorter or longer than the manifest implies is delivered as it
        is, and the consumer's deserialize raises on it as it always
        has."""
        want_crc = read_io is not None and read_io.want_crc

        def work():
            from .. import _native

            size = os.path.getsize(path) if known else n
            # 4096-aligned so the native direct read preads straight into
            # this buffer (zero-copy) instead of bouncing every chunk.
            arr = _native.aligned_empty(size)
            # Its pages' first touch, here and not inside the read, whose
            # faults on a sandboxed kernel hold up every other thread of
            # the restore (see ts_touch_pages).
            _native.touch_pages(arr)
            if want_crc:
                got, crc, algo = _native.read_range_into(
                    path, offset, size, arr, want_crc=True
                )
                return arr, size, got, crc, algo
            got = _read_range(path, offset, size, arr.data)
            return arr, size, got, None, None

        arr, n, got, crc, algo = await self._to_reader(work, n, known)
        if want_crc and got == n:
            read_io.crc32c = crc
            read_io.crc_algo = algo
        view = memoryview(arr)[:got] if got != n else memoryview(arr)
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
