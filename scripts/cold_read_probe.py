#!/usr/bin/env python3
"""How fast does this mount give back a snapshot's blobs cold, and at what width?

No event loop, no scheduler, no device. The blobs of ``--path`` (every file of
``--min-mib`` MiB or more under it: a snapshot's directory), or else
``--blobs`` files of ``--blob-mib`` MiB written under ``--dir`` first, are
evicted from the page cache the way the benchmark's ``resume_loop`` does
(``fsync`` + ``POSIX_FADV_DONTNEED`` a file) and read whole, largest first,
by ``--widths`` threads in turn, each read as the fs plug-in's reader makes
it: ``_native.read_range`` into a fresh ``_native.aligned_empty`` buffer.
One JSON line a width (``read``), with the bytes, the seconds, GB/s and the
median and slowest blob. ``--reuse`` alone reads into one buffer a thread, written
once before the clock starts, instead: what the mount gives when no page of
the destination is touched for the first time (a restore's buffers are fresh).
``--reuse 2,4,8`` passes buffers from one read to the next as a restore could
(``pool`` lines, one a count and width): the threads share at most that many
buffers, none of which exists when the clock starts; a read takes the smallest
free buffer that holds it, else allocates one and touches its pages as the
plug-in does (``_native.touch_pages``) while fewer than the count exist, else
waits for one to come back; a buffer comes back ``--hold-ms`` after its read
ended (the time a consumer keeps it). A count of the number of blobs is what
the plug-in does without a pool: every read touches a buffer of its own.

``--calls K`` adds the two calls a restore used to make on its event
loop's thread between a read's dispatch and its hand-off (``calls`` lines):
``os.path.getsize`` of a blob and ``threading.Thread.start()``, each timed
K times with nothing else running (``alone``) and again beside ``--beside``
threads reading the evicted blobs (``beside_reads``). It says whether the
calls are dear by themselves or only beside reads.

    chiprun -- python3 scripts/cold_read_probe.py --blobs 19 --blob-mib 192 --calls 20
    python3 scripts/cold_read_probe.py --blobs 3 --blob-mib 1 --min-mib 0 --widths 1,2
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import shutil
import statistics
import sys
import tempfile
import threading
import time
from typing import Callable, Dict, List, Tuple

now = time.monotonic
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def evict(paths: List[str]) -> int:
    """Ask the kernel to drop the files' pages (``perf/traffic/resume_loop``
    does the same); returns the bytes asked for."""
    total = 0
    for path in paths:
        fd = os.open(path, os.O_RDONLY)
        try:
            os.fsync(fd)
            os.posix_fadvise(fd, 0, 0, os.POSIX_FADV_DONTNEED)
            total += os.fstat(fd).st_size
        finally:
            os.close(fd)
    return total


def find_blobs(root: str, min_bytes: int) -> List[Tuple[str, int]]:
    """``(path, size)`` of every file of ``min_bytes`` or more under
    ``root``, largest first (the order the read scheduler dispatches in)."""
    found = []
    for dirpath, _, files in os.walk(root):
        for name in files:
            path = os.path.join(dirpath, name)
            size = os.path.getsize(path)
            if size >= min_bytes:
                found.append((path, size))
    return sorted(found, key=lambda b: (-b[1], b[0]))


def write_blobs(root: str, count: int, nbytes: int) -> None:
    import numpy as np

    from tpusnap import _native

    rng = np.random.default_rng(0)
    block = rng.integers(0, 255, min(nbytes, 1 << 20), dtype=np.uint8)
    buf = np.resize(block, nbytes)
    for i in range(count):
        buf[:8] = np.frombuffer(i.to_bytes(8, "little"), dtype=np.uint8)
        path = os.path.join(root, f"blob_{i:03d}")
        if _native.available():
            _native.write_file(path, buf)
        else:
            _native._write_all(path, memoryview(buf))


def _read_on_threads(blobs: List[Tuple[str, int]], width: int, read, per_thread=None) -> Dict[str, float]:
    """Evict the blobs, then ``read(path, size, *mine)`` each once, largest
    first, on ``width`` threads (``mine``: what ``per_thread()`` made for
    the thread before the clock started, if given)."""
    evict([p for p, _ in blobs])
    todo = list(reversed(blobs))  # pop() takes the largest
    lock = threading.Lock()
    each: List[float] = []
    errors: List[BaseException] = []

    def reader(*mine) -> None:
        while True:
            with lock:
                if not todo:
                    return
                path, size = todo.pop()
            t = now()
            try:
                read(path, size, *mine)
            except BaseException as e:
                errors.append(e)
                return
            dt = now() - t
            with lock:
                each.append(dt)

    threads = [
        threading.Thread(target=reader, name=f"probe-read-{i}",
                         args=(per_thread(),) if per_thread else ())
        for i in range(width)
    ]
    t0 = now()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    seconds = now() - t0
    if errors:
        raise errors[0]
    total = sum(s for _, s in blobs)
    return {
        "width": width,
        "blobs": len(blobs),
        "bytes": total,
        "seconds": seconds,
        "gb_per_s": total / seconds / 1e9,
        "blob_median_s": statistics.median(each),
        "blob_max_s": max(each),
    }


def read_all(blobs: List[Tuple[str, int]], width: int, reuse: bool = False) -> Dict[str, float]:
    """Read every blob once on ``width`` threads; the blobs are evicted first."""
    from tpusnap import _native

    def read(path: str, size: int, mine=None) -> None:
        arr = _native.aligned_empty(size) if mine is None else mine[:size]
        got = _native.read_range(path, 0, size, arr.data)
        if got != size:
            raise IOError(f"short read: {got} of {size} bytes from {path}")

    def warm():
        buf = _native.aligned_empty(blobs[0][1])
        buf.fill(1)
        return buf

    return {**_read_on_threads(blobs, width, read, warm if reuse else None), "reuse": reuse}


class _Pool:
    """At most ``count`` buffers, passed from read to read; empty at first."""

    def __init__(self, count: int) -> None:
        self.count = count
        self.cond = threading.Condition()
        self.free: list = []
        self.made = 0
        self.fresh_bytes = self.reused_bytes = 0
        self.wait_s = self.touch_s = self.fresh_read_s = self.reused_read_s = 0.0

    def take(self, size: int):
        """``(buffer, fresh)``."""
        from tpusnap import _native

        with self.cond:
            t = now()
            while True:
                fits = [i for i, b in enumerate(self.free) if b.nbytes >= size]
                if fits:
                    buf = self.free.pop(min(fits, key=lambda i: self.free[i].nbytes))
                    self.reused_bytes += size
                    self.wait_s += now() - t
                    return buf, False
                if self.made < self.count or len(self.free) == self.made:
                    if self.made >= self.count:  # every buffer is free and too small
                        self.free.sort(key=lambda b: b.nbytes)
                        del self.free[0]
                        self.made -= 1
                    self.made += 1
                    self.fresh_bytes += size
                    self.wait_s += now() - t
                    break
                self.cond.wait()
        t = now()
        buf = _native.aligned_empty(size)
        _native.touch_pages(buf)
        with self.cond:
            self.touch_s += now() - t
        return buf, True

    def give(self, buf) -> None:
        with self.cond:
            self.free.append(buf)
            self.cond.notify_all()


def read_pooled(blobs: List[Tuple[str, int]], width: int, count: int, hold_s: float) -> Dict[str, float]:
    """Every blob once on ``width`` threads that share ``count`` buffers; a
    buffer goes back ``hold_s`` after its read ended, on one thread that is
    started before the clock (a thread's start beside reads is dear there)."""
    import queue

    pool = _Pool(count)
    held: "queue.SimpleQueue" = queue.SimpleQueue()

    def holder() -> None:
        while True:
            item = held.get()
            if item is None:
                return
            back_at, buf = item
            time.sleep(max(0.0, back_at - now()))
            pool.give(buf)

    consumer = threading.Thread(target=holder, name="probe-hold")
    consumer.start()

    def read(path: str, size: int) -> None:
        from tpusnap import _native

        buf, fresh = pool.take(size)
        t = now()
        got = _native.read_range(path, 0, size, buf[:size].data)
        with pool.cond:
            if fresh:
                pool.fresh_read_s += now() - t
            else:
                pool.reused_read_s += now() - t
        if got != size:
            raise IOError(f"short read: {got} of {size} bytes from {path}")
        if hold_s:
            held.put((now() + hold_s, buf))
        else:
            pool.give(buf)

    try:
        line = _read_on_threads(blobs, width, read)
    finally:
        held.put(None)
        consumer.join()
    return {
        **line, "buffers": count, "hold_ms": hold_s * 1e3, "buffers_made": pool.made,
        "fresh_bytes": pool.fresh_bytes, "reused_bytes": pool.reused_bytes,
        "wait_s": pool.wait_s, "touch_s": pool.touch_s, "fresh_read_s": pool.fresh_read_s,
        "reused_read_s": pool.reused_read_s,
    }


def _timed(fn: Callable[[], None], k: int) -> List[float]:
    out = []
    for _ in range(k):
        t = now()
        fn()
        out.append((now() - t) * 1e3)
    return out


def time_calls(blobs: List[Tuple[str, int]], k: int, beside: int) -> List[dict]:
    """``os.path.getsize`` and ``Thread.start()`` on this thread, ``k`` times
    each: alone, then beside ``beside`` threads that read the evicted blobs
    (round and round, until the timing is done)."""
    from tpusnap import _native

    paths = [p for p, _ in blobs]
    next_path = itertools.cycle(paths)
    started: List[threading.Thread] = []
    idle = threading.Event()

    def getsize() -> None:
        os.path.getsize(next(next_path))

    def start_thread() -> None:
        t = threading.Thread(target=idle.wait)
        t.start()
        started.append(t)

    def measure(where: str) -> List[dict]:
        return [
            {"where": where, "call": name, "k": k, "median_ms": statistics.median(ms),
             "max_ms": max(ms), "sum_ms": sum(ms)}
            for name, ms in (("getsize", _timed(getsize, k)),
                             ("thread_start", _timed(start_thread, k)))
        ]

    evict(paths)
    lines = measure("alone")
    stop = threading.Event()
    read_bytes = [0]

    def reader(first: int) -> None:
        i = first
        while not stop.is_set():
            path, size = blobs[i % len(blobs)]
            arr = _native.aligned_empty(size)
            read_bytes[0] += _native.read_range(path, 0, size, arr.data)
            i += beside

    evict(paths)
    readers = [threading.Thread(target=reader, args=(i,)) for i in range(beside)]
    for t in readers:
        t.start()
    time.sleep(0.05)  # every reader inside its first read
    t0 = now()
    lines += measure("beside_reads")
    seconds = now() - t0
    stop.set()
    for t in readers:
        t.join()
    idle.set()
    for t in started:
        t.join()
    lines[-1]["readers"] = lines[-2]["readers"] = beside
    lines[-1]["timing_s"] = lines[-2]["timing_s"] = seconds
    return lines


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--path", default=None, help="a snapshot's directory to read")
    parser.add_argument("--dir", default=None, help="where to write blobs (default: TMPDIR)")
    parser.add_argument("--blobs", type=int, default=19)
    parser.add_argument("--blob-mib", type=float, default=192.0)
    parser.add_argument("--min-mib", type=float, default=4.0)
    parser.add_argument("--widths", default="1,2,4,8")
    parser.add_argument("--repeats", type=int, default=1)
    parser.add_argument("--reuse", nargs="?", const="thread", default=None,
                        help="alone: one warm buffer a thread; '2,4,8': buffers passed between reads")
    parser.add_argument("--hold-ms", type=float, default=0.0)
    parser.add_argument("--calls", type=int, default=0)
    parser.add_argument("--beside", type=int, default=8)
    args = parser.parse_args(argv)

    made = None
    root = args.path
    if root is None:
        made = root = tempfile.mkdtemp(prefix="tpusnap_cold_read_", dir=args.dir)
        write_blobs(root, args.blobs, int(args.blob_mib * (1 << 20)))
    try:
        blobs = find_blobs(root, int(args.min_mib * (1 << 20)))
        if not blobs:
            print(f"cold_read_probe: no blob of {args.min_mib} MiB or more under {root}")
            return 2
        print(json.dumps({"probe": "blobs", "root": root, "written_here": made is not None,
                          "count": len(blobs), "bytes": sum(s for _, s in blobs),
                          "largest": blobs[0][1], "smallest": blobs[-1][1]}), flush=True)
        for _ in range(args.repeats):
            for width in (int(w) for w in args.widths.split(",")):
                if args.reuse in (None, "thread"):
                    line = read_all(blobs, width, args.reuse is not None)
                    print(json.dumps({"probe": "read", **line}), flush=True)
                    continue
                for count in (int(c) for c in args.reuse.split(",")):
                    line = read_pooled(blobs, width, count, args.hold_ms / 1e3)
                    print(json.dumps({"probe": "pool", **line}), flush=True)
        if args.calls:
            for line in time_calls(blobs, args.calls, args.beside):
                print(json.dumps({"probe": "calls", **line}), flush=True)
    finally:
        if made is not None:
            shutil.rmtree(made, ignore_errors=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
