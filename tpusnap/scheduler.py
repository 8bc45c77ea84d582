"""Async execution engine: budget-gated, pipelined staging and storage I/O.

TPU-native counterpart of /root/reference/torchsnapshot/scheduler.py.
Semantics preserved:

- Write path (scheduler.py:220-337): each WriteReq becomes a pipeline moving
  ready_for_staging → staging → ready_for_io → io → done. Staging (device→
  host DMA + serialization, in a thread pool with the GIL released by
  numpy/ctypes/XLA) is dispatched only while the outstanding staging cost
  fits the memory budget — but at least one request is always allowed so a
  single over-budget item can't deadlock (scheduler.py:264-275). Storage
  I/O keeps ≤16 requests in flight; the staging executor is sized by
  TPUSNAP_STAGE_THREADS (default 1 — interleaved clone threads measured
  SLOWER in aggregate than one on this memory system).
- ``execute_write_reqs`` returns a ``PendingIOWork`` once the take's
  BLOCKED WINDOW closes. For sync takes and staging-priority async takes
  that is staging-complete (the snapshot is then consistent: buffers no
  longer alias live arrays). For PIPELINED async takes
  (``pipelined_staging=True``) it is first-window-staged: only a
  memory-budget-bounded window of write requests is staged before control
  returns, and the background drain keeps cloning window after window,
  releasing each to storage I/O — blocked time and clone RSS are
  O(window), not O(state). The engine itself is resumable
  (:class:`_WriteScheduler`): the same stage ∥ write loop runs to the
  blocked-window boundary on the caller's thread and to completion inside
  ``PendingIOWork`` (``take`` drains synchronously, ``async_take`` on a
  background thread).
- Read path mirrors it (scheduler.py:357-444): read (≤16 concurrent,
  budget-gated on consuming cost) ∥ consume (deserialize + copy into the
  restore target, thread pool).
- Memory budget = min(0.6 × available host RAM / local_world_size, 32GB),
  env-overridable; local world size discovered by all-gathering hostnames
  (scheduler.py:27-65). Pipelined async takes further clamp their
  in-flight staging budget to TPUSNAP_ASYNC_STAGE_WINDOW_BYTES. The
  budget charges a request what the end of its write gives back: an
  accelerator leaf staged as the host value that the runtime keeps on
  the caller's own array is charged nothing.
"""

from __future__ import annotations

import asyncio
import contextlib
import logging
import socket
import threading
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Callable, Iterator, List, Optional, Set

import psutil

from . import access, flight, telemetry
from .io_types import (
    PROBE_DIR,
    ReadIO,
    ReadReq,
    StoragePlugin,
    WriteIO,
    WriteReq,
    run_on_loop,
    stager_aliases_caller_memory,
    stager_stages_callers_host_value,
    stager_start_dtoh,
    stager_went_cow,
    start_all_workers,
)
from .knobs import get_memory_budget_override_bytes

logger = logging.getLogger(__name__)

import os as _os

_MAX_IO_CONCURRENCY = 16
# How far ahead of the request it dispatches the write scheduler starts
# copies to the host (`_WriteScheduler._start_dtoh_ahead`) where a step
# of the caller's may run beside them (`_steps_may_run`): the
# dispatched request's own and the next one's always, further ones while
# the bytes started and not yet staged are under this many. On the TPU
# runtime a program dispatched after a copy waits behind it, and how long
# follows the leaves in flight (scripts/dtoh_overlap_probe.py, on a v5e:
# 16 steps of 80 ms beside 3.76 GB of copies cost +0.8 s with every copy
# started at once; with leaves of 128-384 MiB +0.2-0.5 s one leaf ahead
# and +0.6-0.9 s two ahead; leaves of 64 MiB nothing at eight in flight,
# and they reach the host at 1.6 GB/s two in flight, 2.9 GB/s at eight).
_DTOH_LOOKAHEAD_REQS = 1
_DTOH_LOOKAHEAD_BYTES = 256 * 1024 * 1024
# The same depth where no step can run beside the copies (the caller
# stands in the take, or in `wait_staged()`), and so nobody is held up
# behind them; the take's host-memory budget where that is less. Copies
# queued together share the bus, so each is seen on the host later than
# it would be alone and its write starts later: on a v5e a state of 4.86
# GB in leaves of 192-256 MiB was all on the host 1.55-1.68 s after
# `async_take`'s return at 256 MiB, 1.42-1.51 s at 1 GiB and 1.35-1.46 s
# with every copy queued, and durable after 2.2-2.4, 2.4-2.7 and 3.0-4.1
# s (PERF.md 6, PR 52): past four leaves the wait gains little and the
# time to durable pays for it.
_DTOH_LOOKAHEAD_BYTES_NO_STEPS = 1024 * 1024 * 1024
# Staging/consume threads do memory-bandwidth work (memcpy, CRC,
# deserialize) with the GIL released; more threads than cores only adds
# GIL ping-pong and context switching (measured on the 1-vCPU dev host:
# 4 interleaved clone threads ran ~1 GB/s aggregate vs ~4 GB/s for one).
_MAX_CPU_CONCURRENCY = max(1, min(4, _os.cpu_count() or 4))
_MAX_PER_RANK_MEMORY_BUDGET_BYTES = 32 * 1024 * 1024 * 1024
_AVAILABLE_MEMORY_FRACTION = 0.6
_REPORT_INTERVAL_SEC = 10.0


# local_world_size is stable for the life of a job; cache it so restore
# and read_object never pay a collective for it (take threads it through
# explicitly from its coalescing gather).
_cached_local_world_size: Optional[int] = None


def get_process_memory_budget_bytes(
    comm=None, local_world_size: Optional[int] = None
) -> int:
    """Per-process host-memory budget for staging/consuming buffers
    (reference scheduler.py:45-65). ``local_world_size`` (ranks sharing
    this host) may be passed by callers that already gathered hostnames;
    otherwise it is discovered once per process and cached."""
    global _cached_local_world_size
    override = get_memory_budget_override_bytes()
    if override is not None:
        return override
    if local_world_size is not None:
        _cached_local_world_size = local_world_size
    elif _cached_local_world_size is not None:
        local_world_size = _cached_local_world_size
    elif comm is not None and comm.world_size > 1:
        from .knobs import get_node_name

        hostnames = comm.all_gather_object(get_node_name())
        local_world_size = hostnames.count(get_node_name())
        _cached_local_world_size = local_world_size
    else:
        local_world_size = 1
    available = psutil.virtual_memory().available
    budget = int(available * _AVAILABLE_MEMORY_FRACTION / max(local_world_size, 1))
    return min(budget, _MAX_PER_RANK_MEMORY_BUDGET_BYTES)


async def _cancel_and_drain(tasks: Set[asyncio.Task]) -> None:
    """Abort helper shared by the write loop and PendingIOWork: cancel
    in-flight tasks and await them so the loop can close cleanly and no
    write keeps running into an aborted snapshot directory."""
    for task in tasks:
        task.cancel()
    if tasks:
        await asyncio.gather(*tasks, return_exceptions=True)


# Stats of the most recent completed write/read execution in this process,
# keyed by verb ("write"/"read"). Benchmarks and tests read this to get the
# staging-time vs total-time split without parsing logs.
LAST_EXECUTION_STATS: dict = {}


class _Reporter:
    """Periodic pipeline progress logging (reference scheduler.py:96-175):
    per-stage pipeline counts, RSS delta, remaining memory budget, and a
    staging-time vs total-time summary — the observability needed to tell
    a staging-bound pipeline from an I/O-bound one."""

    def __init__(self, rank: int, verb: str, total_reqs: int) -> None:
        self.rank = rank
        self.verb = verb
        self.total_reqs = total_reqs
        self.begin_ts = time.monotonic()
        self.last_report_ts = self.begin_ts
        self.bytes_done = 0
        self.reqs_done = 0
        self.rss_begin = psutil.Process().memory_info().rss
        self.staging_done_ts: Optional[float] = None
        # Live pipeline-stage counts, updated by the execution loop:
        # {stage: count} with stages ready_for_staging/staging/ready_for_io/io.
        self.stage_counts: dict = {}
        self.budget_remaining: Optional[int] = None
        self.total_budget: Optional[int] = None
        # Pipelined async takes: wall-clock of the blocked window (first
        # window staged, control returned) and how many staging windows
        # the take ran in total.
        self.blocked_done_ts: Optional[float] = None
        self.stage_windows: Optional[int] = None

    def mark_staging_complete(self) -> None:
        if self.staging_done_ts is None:
            self.staging_done_ts = time.monotonic()

    def mark_blocked_window_done(self) -> None:
        if self.blocked_done_ts is None:
            self.blocked_done_ts = time.monotonic()

    def report_request_done(self, nbytes: int) -> None:
        self.reqs_done += 1
        self.bytes_done += nbytes
        now = time.monotonic()
        if now - self.last_report_ts >= _REPORT_INTERVAL_SEC:
            self.last_report_ts = now
            rss_delta = psutil.Process().memory_info().rss - self.rss_begin
            counts = " ".join(
                f"{k}={v}" for k, v in self.stage_counts.items()
            )
            budget = (
                f", budget {self.budget_remaining / 1e9:.1f}/"
                f"{self.total_budget / 1e9:.1f} GB free"
                if self.budget_remaining is not None
                and self.total_budget is not None
                else ""
            )
            logger.info(
                "Rank %d: %s %d/%d reqs [%s done=%d], %.2f GB, %.1f MB/s, "
                "rss delta %.0f MB%s",
                self.rank,
                self.verb,
                self.reqs_done,
                self.total_reqs,
                counts,
                self.reqs_done,
                self.bytes_done / 1e9,
                self.bytes_done / 1e6 / max(now - self.begin_ts, 1e-9),
                rss_delta / 1e6,
                budget,
            )

    def summarize(self) -> None:
        end_ts = time.monotonic()
        elapsed = max(end_ts - self.begin_ts, 1e-9)
        staging_elapsed = (
            max(self.staging_done_ts - self.begin_ts, 0.0)
            if self.staging_done_ts is not None
            else None
        )
        stats = {
            "reqs": self.reqs_done,
            "bytes": self.bytes_done,
            "total_s": elapsed,
            "staging_s": staging_elapsed,
            "throughput_mbps": self.bytes_done / 1e6 / elapsed,
            "budget_bytes": self.total_budget,
        }
        if self.blocked_done_ts is not None:
            stats["blocked_s"] = max(self.blocked_done_ts - self.begin_ts, 0.0)
        if self.stage_windows is not None:
            stats["stage_windows"] = self.stage_windows
        LAST_EXECUTION_STATS[self.verb] = stats
        if staging_elapsed is not None:
            # The number async_take exists to minimize: training is blocked
            # only for the staging window, not the full I/O drain.
            logger.info(
                "Rank %d: %s complete: %d reqs, %.2f GB in %.2fs "
                "(%.1f MB/s); staging %.2fs / residual I/O %.2fs",
                self.rank,
                self.verb,
                self.reqs_done,
                self.bytes_done / 1e9,
                elapsed,
                self.bytes_done / 1e6 / elapsed,
                staging_elapsed,
                elapsed - staging_elapsed,
            )
        else:
            logger.info(
                "Rank %d: %s complete: %d reqs, %.2f GB in %.2fs (%.1f MB/s)",
                self.rank,
                self.verb,
                self.reqs_done,
                self.bytes_done / 1e9,
                elapsed,
                self.bytes_done / 1e6 / elapsed,
            )


class _ProbeRunner:
    """In-take/in-restore roofline probes (``TPUSNAP_PROBE=1``):
    between I/O windows — once per TPUSNAP_PROBE_INTERVAL_BYTES of
    payload traffic, while no blob I/O is in flight — write (then read
    back, then delete) TPUSNAP_PROBE_BYTES of raw data through the
    operation's OWN storage plugin stack, across a few concurrent
    streams, and record the aggregate throughput as a probe sample.
    Each sample times BOTH legs: the take's summary derives
    ``roofline_fraction`` from the write leg, the restore's
    ``restore_roofline_fraction`` from the read leg — ceilings measured
    seconds (not minutes) from the I/O they judge, immune to the
    multi-minute disk drift that made separate full-scale roofline
    sessions scatter 3x (ROADMAP 5a). On the restore side the probe
    still writes its own scratch (the snapshot's blobs are immutable),
    under ``.tpusnap/probe/`` (journal-exempt sidecar space; a crash's
    leftovers are orphan-visible to fsck/gc). Failures never fail the
    take or restore — a failed probe is one missing sample."""

    _STREAMS = 4

    def __init__(
        self,
        storage: StoragePlugin,
        rank: int,
        tele: telemetry.TakeTelemetry,
    ) -> None:
        from .knobs import get_probe_bytes, get_probe_interval_bytes

        self.storage = storage
        self.rank = rank
        self.tele = tele
        self.interval_bytes = get_probe_interval_bytes()
        self.stream_bytes = max(get_probe_bytes() // self._STREAMS, 1 << 20)
        self.bytes_since_probe = 0
        self.ran = 0
        self._buf: Optional[memoryview] = None
        self._failed = False
        from . import compress as _compress

        try:
            # Same device/bucket-scoped key the auto policy looks up —
            # NOT the bare class label (two fs:// mounts with different
            # bandwidth must not share a ceiling sample).
            self._label = _compress.pipe_ceiling_key(storage)
        except Exception:
            self._label = ""

    @property
    def due(self) -> bool:
        return not self._failed and self.bytes_since_probe >= self.interval_bytes

    def note_written(self, nbytes: int) -> None:
        self.bytes_since_probe += nbytes

    def _buffer(self) -> memoryview:
        if self._buf is None:
            # Random-ish payload (tiled 1 MiB urandom block): constant
            # fill could be flattered by host-side image compression
            # and would not match what the take writes.
            block = _os.urandom(1 << 20)
            reps = (self.stream_bytes + len(block) - 1) // len(block)
            self._buf = memoryview(block * reps)[: self.stream_bytes]
        return self._buf

    def _path(self, i: int) -> str:
        return f"{PROBE_DIR}/rank_{self.rank}_{i}.bin"

    async def run(self) -> None:
        """One probe segment. Caller guarantees no blob I/O in flight
        (the scheduler parks its I/O gate until the window drains), so
        the sample measures the engine, not contention with the take."""
        self.bytes_since_probe = 0
        start = self.tele.now()
        nbytes = self.stream_bytes * self._STREAMS
        try:
            buf = self._buffer()
            paths = [self._path(i) for i in range(self._STREAMS)]
            t0 = time.monotonic()
            await asyncio.gather(
                *(self.storage.write(WriteIO(path=p, buf=buf)) for p in paths)
            )
            write_s = time.monotonic() - t0
            t0 = time.monotonic()
            await asyncio.gather(
                *(self.storage.read(ReadIO(path=p)) for p in paths)
            )
            read_s = time.monotonic() - t0
            await asyncio.gather(
                *(self.storage.delete(p) for p in paths),
                return_exceptions=True,
            )
        except Exception:
            # One WARNING, then stand down for this take: a backend
            # that cannot take probe traffic must not eat a retry storm.
            # Best-effort cleanup of any stream that did land (a
            # leftover would only be orphan debris for gc, but tidy is
            # cheaper than debris).
            self._failed = True
            logger.warning(
                "Rank %d: in-take roofline probe failed (non-fatal; "
                "disabled for the rest of this take)",
                self.rank,
                exc_info=True,
            )
            try:
                await asyncio.gather(
                    *(
                        self.storage.delete(self._path(i))
                        for i in range(self._STREAMS)
                    ),
                    return_exceptions=True,
                )
            except Exception:
                pass
            return
        elapsed = self.tele.now() - start
        sample = {
            "write_gbps": round(nbytes / max(write_s, 1e-9) / 1e9, 4),
            "read_gbps": round(nbytes / max(read_s, 1e-9) / 1e9, 4),
            "bytes": nbytes,
            "elapsed_s": round(elapsed, 6),
        }
        self.ran += 1
        # Feed the compression auto policy's ceiling registry: every
        # probe sample keeps the pipe ceiling a live measurement, so
        # the next take's compress-or-bypass decision is current.
        from . import compress as _compress

        _compress.note_pipe_ceiling(self._label, sample["write_gbps"])
        _compress.note_pipe_ceiling(
            self._label, sample["read_gbps"], lane="read"
        )
        self.tele.add_probe_sample(sample)
        self.tele.record_span("probe_roofline", start, elapsed, **sample)
        telemetry.incr("probe.probes", rec=self.tele)
        telemetry.incr("probe.bytes_written", nbytes, rec=self.tele)
        flight.record(
            "probe",
            write_gbps=sample["write_gbps"],
            read_gbps=sample["read_gbps"],
            bytes=nbytes,
        )


@dataclass
class PendingIOWork:
    """Work remaining after the blocked window closed (reference
    scheduler.py:178-217). ``complete`` resumes the same stage ∥ write
    engine: residual STAGING windows of a pipelined async take first
    (interleaved with their storage I/O), then the I/O drain — honoring
    the same budget and concurrency caps throughout."""

    scheduler: "_WriteScheduler"

    def staging_complete(self) -> bool:
        """Whether ALL staging is done (buffers no longer alias live
        arrays). True at construction except for pipelined async takes,
        whose residual windows stage inside ``complete``."""
        return self.scheduler.staging_complete

    def wait_staged(self, timeout: Optional[float] = None) -> bool:
        return self.scheduler.staging_done_event.wait(timeout)

    def went_cow(self) -> bool:
        """Whether a stager of this take staged the caller's live bytes
        under copy-on-write (``io_types.stager_went_cow``). Every stager
        has decided by staging-complete; before it the answer may still
        turn true."""
        return self.scheduler.went_cow

    def safe_to_mutate(self) -> bool:
        """Whether no byte the caller can write or delete is read any
        more: staging is complete and, where a stager went copy-on-write
        (its blob is written from the live memory and verified after),
        THIS RANK's writes have drained too. Read off what the take did,
        not off ``TPUSNAP_ASYNC_COW``: on an accelerator no stager goes
        copy-on-write, and the staged host values are out of the caller's
        reach."""
        return self.staging_complete() and (
            not self.went_cow() or self.drained()
        )

    @contextlib.contextmanager
    def caller_waits(self) -> Iterator[None]:
        """Around a caller's wait for the staging
        (``PendingSnapshot.wait_staged``): a thread that stands there
        dispatches no step, which the scheduler reads as it starts the
        copies to the host (``_WriteScheduler._steps_may_run``). The
        drain is told at once (a callback on its loop, which may start
        every remaining copy now: ``_start_dtoh_ahead``), not at its
        next completion: that is a whole leaf's crossing away, and until
        then only the copies started for a caller that might step are
        under way."""
        scheduler = self.scheduler
        with scheduler.waiting_lock:
            scheduler.callers_waiting += 1
        scheduler.look_again()
        try:
            yield
        finally:
            with scheduler.waiting_lock:
                scheduler.callers_waiting -= 1

    def drained(self) -> bool:
        """Whether THIS RANK's write drain (all writes + COW verifies)
        finished — under COW this, not staging-complete, is when live
        bytes stop being read."""
        return self.scheduler.drained_event.is_set()

    def wait_drained(self, timeout: Optional[float] = None) -> bool:
        return self.scheduler.drained_event.wait(timeout)

    async def complete(self) -> None:
        await self.scheduler.drain()

    def sync_complete(self, event_loop: asyncio.AbstractEventLoop) -> None:
        # run_on_loop: the commit path reuses this loop for the metadata
        # write and close afterwards — a stranded task would be resumed
        # mid-commit.
        run_on_loop(event_loop, self.complete())


class _WritePipeline:
    def __init__(
        self,
        write_req: WriteReq,
        storage: StoragePlugin,
        executor: Optional[ThreadPoolExecutor] = None,
        hash_executor: Optional[ThreadPoolExecutor] = None,
        tele: Optional[telemetry.TakeTelemetry] = None,
    ) -> None:
        self.write_req = write_req
        self.storage = storage
        self.executor = executor
        self.tele = tele
        # Deferred checksums run here, NEVER on the staging executor:
        # queued hash jobs behind staging tasks would stall staging
        # completion — the async blocked window — behind work that was
        # deferred precisely to leave that window (measured at 20 GB:
        # staging_s 50 s of a 52 s take with the shared 1-worker pool).
        self.hash_executor = hash_executor or executor
        self.staging_cost = write_req.buffer_stager.get_staging_cost_bytes()
        self.buf = None
        self.buf_size = 0
        # True when the stager reported the content is already persisted
        # (incremental dedup): the request completes with no storage I/O.
        self.skipped = False
        # Whether a pipelined async take must stage this request before
        # it returns (_WriteScheduler decides; every other mode: all).
        self.in_window = True
        # Bytes of this request's copy to the host that the scheduler
        # started and staging has not fetched yet.
        self.dtoh_unfetched = 0
        # What of the staging budget this request holds now: its
        # staging cost from the dispatch, its staged buffer's size from
        # staging's end to its write's; nothing, all along, where it
        # stages the caller's own host value.
        self.charged = 0

    async def stage(self, executor: ThreadPoolExecutor) -> "_WritePipeline":
        from .io_types import SKIP_WRITE

        # The request's await, timed on the event-loop thread (kind wait):
        # it holds the wait for a staging thread, the work and the loop's
        # own latency; the hand-off's `stage.queued` / `stage.work` spans,
        # recorded on the worker as children of this one, tell them apart.
        with telemetry.span(
            "stage_buffer",
            kind=telemetry.WAIT,
            rec=self.tele,
            path=self.write_req.path,
            bytes=self.staging_cost,
        ):
            buf = await self.write_req.buffer_stager.stage_buffer(executor)
        if buf is SKIP_WRITE:
            self.skipped = True
            telemetry.incr("scheduler.dedup_skipped", rec=self.tele)
            # Byte-grain leg of the skip counter: the dual-hash pass
            # proved these planned payload bytes unchanged against the
            # base — the SLO tracker's data-at-risk accounting subtracts
            # them live (tpusnap.slo).
            telemetry.incr(
                "scheduler.dedup_skipped_bytes",
                self.write_req.buffer_stager.get_planned_bytes(),
                rec=self.tele,
            )
            return self
        self.buf = buf
        self.buf_size = (
            memoryview(self.buf).cast("B").nbytes if self.buf is not None else 0
        )
        return self

    async def write(self) -> "_WritePipeline":
        stager = self.write_req.buffer_stager
        if getattr(stager, "defer_checksums", False) and self.buf is not None:
            # Deferred hashing (single-process, non-incremental takes):
            # checksums computed HERE, on the write path — overlapping
            # other requests' disk time instead of occupying the staging
            # window async_take blocks training on. The values land in
            # the same entry objects the manifest references, before the
            # post-drain metadata commit.
            late = getattr(stager, "late_checksum", None)
            if late is not None:
                hash_start = self.tele.now() if self.tele is not None else 0.0
                loop = asyncio.get_running_loop()
                if self.hash_executor is not None:
                    await loop.run_in_executor(
                        self.hash_executor, late, self.buf
                    )
                else:
                    late(self.buf)
                if self.tele is not None:
                    self.tele.record_span(
                        "checksum_late",
                        hash_start,
                        self.tele.now() - hash_start,
                        bytes=self.buf_size,
                    )
        with telemetry.span(
            "storage_write",
            kind=telemetry.WAIT,
            rec=self.tele,
            path=self.write_req.path,
            bytes=self.buf_size,
        ):
            await self.storage.write(
                WriteIO(path=self.write_req.path, buf=self.buf)
            )
        telemetry.incr("storage.bytes_written", self.buf_size, rec=self.tele)
        telemetry.incr("storage.writes", rec=self.tele)
        if getattr(stager, "cow_pending", False):
            # Copy-on-write staging (TPUSNAP_ASYNC_COW): the buffer just
            # written IS the live array — re-hash it and compare with
            # the checksum recorded inside the blocked window. A
            # mismatch means the caller mutated the array mid-take; the
            # take must fail loudly rather than commit torn bytes.
            cow_start = self.tele.now() if self.tele is not None else 0.0
            loop = asyncio.get_running_loop()
            if self.hash_executor is not None:
                await loop.run_in_executor(
                    self.hash_executor, stager.verify_cow_after_write, self.buf
                )
            else:
                stager.verify_cow_after_write(self.buf)
            if self.tele is not None:
                self.tele.record_span(
                    "cow_verify",
                    cow_start,
                    self.tele.now() - cow_start,
                    bytes=self.buf_size,
                )
        # Async-clone buffers go back to the staging pool (warm pages
        # for the next clone of this size); other buffers are ignored by
        # release(). The pool is bounded by TPUSNAP_STAGING_POOL_BYTES,
        # not by this take's budget — see execute_write_reqs.
        from ._staging_pool import release

        release(self.buf)
        self.buf = None  # release host memory
        return self


class _WriteScheduler:
    """Resumable budget-gated stage ∥ write engine behind every take.

    One instance owns the whole pipeline state (request queue, in-flight
    staging/IO task sets, budget). ``run_blocked_window`` advances it to
    the take's blocked-window boundary on the calling thread;
    ``drain`` (via :class:`PendingIOWork`) resumes the SAME loop — on
    the same event loop, possibly from a background thread — until every
    request is staged AND written. A request's copy to the host is
    started when the dispatch reaches it, a fixed depth ahead of the
    thread that fetches it (``_start_dtoh_ahead``): never for the whole
    state at once, and four times as deep where no step of the
    caller's can run beside the copies (the caller stands in the take,
    or in ``wait_staged()``). While a caller waits
    for the staging the take runs at the bus's pace and storage's pace
    is behind it: the leaves it waits for cross as they lie, their host
    values stay with the caller's arrays and are not charged to the
    staging budget, and the wait ends at staging-complete unless a
    stager went copy-on-write (``PendingIOWork.safe_to_mutate``). Three modes:

    - default (sync takes): blocked window = staging complete, staging
      and storage I/O fully overlapped throughout (the metric is total
      time; disk DMA waits overlap staging profitably even on one core).
    - ``prioritize_staging`` (incremental async takes, whose dedup
      decisions must be final before the manifest gather): blocked
      window = staging complete, and NO storage I/O is dispatched while
      staging can still proceed — concurrent write-path work (checksums,
      bounce copies, syscalls) steals core time from the staging pass
      and stretches the blocked window several-fold (measured 2.8s vs a
      0.5s pure clone pass on the 1-core dev host). I/O IS dispatched
      mid-staging when staging is budget-starved (writes must complete
      to free budget — deadlock freedom).
    - ``pipelined_staging`` (async takes): the in-flight staging budget
      is clamped to TPUSNAP_ASYNC_STAGE_WINDOW_BYTES and the blocked
      window ends at FIRST-WINDOW-STAGED — the engine has staged one
      window's worth of the requests that count towards it; the
      drain then clones window N+1 while window N's writes release
      buffers (and budget) back, so blocked time and clone RSS are both
      O(window) instead of O(state). A request counts when its bytes
      may alias memory the caller can write in place once control is
      back (``BufferStager.aliases_caller_memory``; the window exists
      for that caller), or when ``stage_eagerly`` selects it
      (multi-process takes: stagers that annotate manifest entries at
      stage time, whose values would otherwise miss the by-value
      manifest gather). Every other request — an accelerator-resident
      array, held by reference — is queued behind those for the drain;
      with none that counts the window dispatches what the budget
      admits and closes at once. The I/O gate stays shut during the
      blocked window exactly as in prioritize mode, and opens
      permanently once control returns.
    """

    def __init__(
        self,
        write_reqs: List[WriteReq],
        storage: StoragePlugin,
        memory_budget_bytes: int,
        rank: int,
        prioritize_staging: bool = False,
        pipelined_staging: bool = False,
        stage_eagerly: Optional[Callable[[WriteReq], bool]] = None,
        tele: Optional[telemetry.TakeTelemetry] = None,
    ) -> None:
        from .knobs import (
            get_async_stage_window_bytes,
            get_stage_threads,
            is_probe_enabled,
        )

        self.storage = storage
        self.rank = rank
        # In-take roofline probes: only with an enabled recorder (their
        # whole output is telemetry) and the opt-in knob.
        self.probe: Optional[_ProbeRunner] = (
            _ProbeRunner(storage, rank, tele)
            if tele is not None and tele.enabled and is_probe_enabled()
            else None
        )
        self.prioritize_staging = prioritize_staging
        self.pipelined = pipelined_staging
        self.tele = tele
        # TPUSNAP_STAGE_THREADS sizes BOTH the executor and the dispatch
        # cap: staging threads do memory-bandwidth work (memcpy, CRC,
        # deserialize) with the GIL released, and more threads than the
        # memory system feeds only adds cache ping-pong (measured on the
        # 1-vCPU dev host: 4 interleaved clone threads ran ~1 GB/s
        # aggregate vs ~4 GB/s for one).
        self.stage_concurrency = get_stage_threads()
        self.executor = ThreadPoolExecutor(
            max_workers=self.stage_concurrency,
            thread_name_prefix="tpusnap-stage",
        )
        # Deferred write-path hashing gets its own pool so it can never
        # queue ahead of staging tasks (see _WritePipeline.hash_executor).
        self.hash_executor = ThreadPoolExecutor(
            max_workers=_MAX_CPU_CONCURRENCY, thread_name_prefix="tpusnap-hash"
        )
        self.reporter = _Reporter(
            rank=rank, verb="write", total_reqs=len(write_reqs)
        )
        pls = [
            _WritePipeline(wr, storage, self.executor, self.hash_executor, tele)
            for wr in write_reqs
        ]
        # Who must be staged before a pipelined async take returns: the
        # eager set, then every request whose bytes the caller could
        # write in place afterwards. The rest (accelerator-resident
        # arrays, held by reference) is queued behind them for the drain.
        # Within each group, large first — they occupy budget longest
        # and their I/O overlaps the staging of everything behind them.
        eager: List[_WritePipeline] = []
        held: List[_WritePipeline] = []
        released: List[_WritePipeline] = []
        for p in pls:
            if not self.pipelined:
                held.append(p)
            elif stage_eagerly is not None and stage_eagerly(p.write_req):
                eager.append(p)
            elif stager_aliases_caller_memory(p.write_req.buffer_stager):
                held.append(p)
            else:
                p.in_window = False
                released.append(p)
        self.pipelines = deque(
            p
            for group in (eager, held, released)
            for p in sorted(group, key=lambda p: p.staging_cost, reverse=True)
        )
        # Identity set, not a count: with TPUSNAP_STAGE_THREADS >= 2
        # an interleaved NON-eager stager can complete first, and a
        # bare countdown would let the blocked window close while an
        # eager (manifest-annotating) stager is still in flight.
        self.eager_pending = {id(p) for p in eager}
        # The requests not yet asked to start their copy to the host: a
        # suffix of ``pipelines``, shorter by the lookahead.
        self._dtoh_ahead = deque(self.pipelines)
        self.dtoh_unfetched_bytes = 0
        total_cost = sum(p.staging_cost for p in pls)
        released_cost = sum(p.staging_cost for p in released)
        if self.pipelined or prioritize_staging:
            # An async take, whichever mode: what the return waits for
            # (before the window's clamp below) and what it does not.
            telemetry.incr(
                "scheduler.window_held_bytes",
                total_cost - released_cost,
                rec=tele,
            )
            telemetry.incr(
                "scheduler.window_released_bytes", released_cost, rec=tele
            )
        # What the process may hold on the host for this take, before the
        # window's clamp: it bounds the copies started ahead while no
        # step can run beside them (``_start_dtoh_ahead``).
        self.host_budget_bytes = memory_budget_bytes
        if self.pipelined:
            window = get_async_stage_window_bytes()
            if window is not None:
                # The window IS the effective in-flight staging budget:
                # resident clone bytes never exceed it (plus the ≥1
                # over-budget admission), whatever the host-RAM budget
                # would allow.
                memory_budget_bytes = min(memory_budget_bytes, window)
        # The budget governs IN-FLIGHT staging buffers of tpusnap's own:
        # a dispatch debits staging_cost, a write completion credits
        # buf_size. A request that stages the host value kept on the
        # caller's own array (``BufferStager.stages_callers_host_value``:
        # an accelerator leaf that crosses as it lies) is debited and
        # credited nothing: the end of its write frees no byte, so
        # holding its staging back for the writers would bound nothing
        # (``scheduler.uncharged_bytes`` counts what went so). Every
        # other request is charged unconditionally. Buffers the staging pool retains after a
        # write are NOT withheld from the credit (ADVICE r4: withholding
        # re-debited the same resident bytes every reuse cycle, and a
        # budget-capped take whose cumulative clone bytes exceeded the
        # budget degraded to fully serialized stage-then-write) — the
        # pool is its own separately bounded cache: worst-case resident
        # is budget + TPUSNAP_STAGING_POOL_BYTES, and in practice ≈
        # budget, because acquire() reuses parked buffers of recurring
        # sizes (uniform chunk sizes within a take — which is also what
        # lets window N+1's clones recycle window N's released buffers
        # so steady-state windows allocate nothing).
        self.memory_budget_bytes = memory_budget_bytes
        self.budget = memory_budget_bytes
        self.reporter.total_budget = memory_budget_bytes
        # First-window target: the blocked window stages at least this
        # much staging cost of the requests that count towards it (all
        # of them, when they fit the window; nothing, when there is none).
        self.first_window_target = min(
            memory_budget_bytes, total_cost - released_cost
        )
        self.window_staged_cost = 0
        self.staging_tasks: Set[asyncio.Task] = set()
        self.io_tasks: Set[asyncio.Task] = set()
        self.ready_for_io: List[_WritePipeline] = []
        self.staged_cost_total = 0
        # I/O gate state for pipelined mode: shut during the blocked
        # window, open forever after.
        self.blocked = self.pipelined
        self.staging_complete = False
        self.staging_done_event = threading.Event()
        # Set when THIS RANK's write drain (all writes + COW verifies)
        # finishes — the COW-mode safe-to-mutate boundary, strictly
        # earlier than the cross-rank commit barrier.
        self.drained_event = threading.Event()
        # Whether a stager staged the caller's live bytes under
        # copy-on-write: read off each request as its staging ends, so
        # final at staging-complete (``PendingIOWork.safe_to_mutate``).
        self.went_cow = False
        # Threads inside ``PendingSnapshot.wait_staged()`` now
        # (``PendingIOWork.caller_waits``).
        self.callers_waiting = 0
        self.waiting_lock = threading.Lock()
        # The loop that drives this take, from its first turn
        # (``look_again``).
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._stall_start: Optional[float] = None
        self._stage_phase_start = tele.now() if tele is not None else 0.0
        self._window_index = 0
        self._window_start = self._stage_phase_start
        self._window_accum = 0

    # --- dispatch ------------------------------------------------------

    def _dispatch_staging(self) -> None:
        while self.pipelines and len(self.staging_tasks) < self.stage_concurrency:
            head = self.pipelines[0]
            # The ≥1 over-budget admission may only fire when NOTHING
            # can free budget: staged buffers waiting in ready_for_io
            # count in EVERY mode — they hold budget that the write
            # dispatched on the next loop turn will credit back.
            # Admitting over budget past them held every staged buffer
            # resident at once (observed as peak 3/2 budget whenever all
            # in-flight stagings completed in one wait batch before any
            # I/O was dispatched) and unenforced the budget entirely.
            in_flight = self.staging_tasks or self.io_tasks or self.ready_for_io
            if self._charge(head) > self.budget and in_flight:
                break  # wait for memory to free up
            self.pipelines.popleft()
            self._start_dtoh_ahead()
            # Its copy has been started by now, so its stager knows how
            # the leaf crosses (the first request of a take is the one
            # whose copy the line above started: it was admitted as
            # charged, and any take admits its first).
            self._settle(head, self._charge(head))
            self.staging_tasks.add(
                asyncio.ensure_future(head.stage(self.executor))
            )

    def _charge(self, pipeline: "_WritePipeline") -> int:
        """What dispatching ``pipeline`` debits: its staging cost, or
        nothing where it stages the caller's own host value."""
        if stager_stages_callers_host_value(pipeline.write_req.buffer_stager):
            return 0
        return pipeline.staging_cost

    def _settle(self, pipeline: "_WritePipeline", holds: int) -> None:
        """``pipeline`` holds ``holds`` bytes of the budget from now on,
        whatever it held before."""
        self.budget += pipeline.charged - holds
        pipeline.charged = holds
        if self.tele is not None:
            # High-water mark of budget in use (can exceed the budget
            # via the ≥1 over-budget admission, or where staging turned
            # a leaf that its device's layout had not foretold).
            self.tele.gauge_max(
                "scheduler.budget_used_bytes",
                self.memory_budget_bytes - self.budget,
            )

    def look_again(self) -> None:
        """From any thread: have the loop that drives this take run the
        lookahead now, because what it reads has changed (a caller has
        come to wait). Nothing before the loop's first turn, which reads
        it anyway, and nothing once the loop is closed."""
        loop = self._loop
        if loop is None:
            return
        try:
            loop.call_soon_threadsafe(self._start_dtoh_ahead)
        except RuntimeError:
            pass  # closed: the take is over

    def _steps_may_run(self) -> bool:
        """Whether the caller may dispatch steps while the copy about to
        start crosses: only a pipelined async take hands control back
        before the staging is complete (every other take's caller stands
        in the take until then), and a caller that has come back to wait
        for the staging (``wait_staged()``, before a step that donates)
        stands still again. A copy on the chip protects those steps and
        costs the transfer behind it the copy's time, so it is made only
        where they may run."""
        return self.pipelined and not self.callers_waiting

    def _start_dtoh_ahead(self) -> None:
        """Start the copy to the host of the request just dispatched
        (already off ``pipelines``) and of those that follow it in the
        queue, ``_DTOH_LOOKAHEAD_REQS`` of them at least and further
        while the bytes started and not yet staged are under the depth:
        ``_DTOH_LOOKAHEAD_BYTES`` where the caller's steps may run
        beside the copies (a step dispatched after a copy waits behind
        it), ``_DTOH_LOOKAHEAD_BYTES_NO_STEPS`` or the take's
        host-memory budget, whichever is less, where none can (nobody
        is there to be held up, and the bus is kept full;
        ``dtoh.deep_starts`` / ``dtoh.deep_bytes`` count what was
        started past the stepping depth). By position in the queue, whether or not the staging
        budget admits those requests yet: this is the prefetch, and the
        depth is what bounds the host copies that the runtime holds
        outside that budget. With more than one staging thread the
        depth counts from the last request dispatched."""
        beside_steps = self._steps_may_run()
        depth = (
            _DTOH_LOOKAHEAD_BYTES
            if beside_steps
            else min(_DTOH_LOOKAHEAD_BYTES_NO_STEPS, self.host_budget_bytes)
        )
        while self._dtoh_ahead:
            # -1: the dispatched request itself, not asked before.
            ahead = len(self.pipelines) - len(self._dtoh_ahead)
            further = ahead >= _DTOH_LOOKAHEAD_REQS
            if further and self.dtoh_unfetched_bytes >= depth:
                break
            deep = further and self.dtoh_unfetched_bytes >= _DTOH_LOOKAHEAD_BYTES
            pipeline = self._dtoh_ahead.popleft()
            started = stager_start_dtoh(
                pipeline.write_req.buffer_stager, beside_steps
            )
            if not started:
                continue
            pipeline.dtoh_unfetched = started
            self.dtoh_unfetched_bytes += started
            if ahead >= 0:
                telemetry.incr("dtoh.lookahead_starts", rec=self.tele)
            if deep:
                telemetry.incr("dtoh.deep_starts", rec=self.tele)
                telemetry.incr("dtoh.deep_bytes", started, rec=self.tele)
            if self.tele is not None:
                self.tele.gauge_max(
                    "dtoh.unfetched_bytes", self.dtoh_unfetched_bytes
                )

    def _staging_budget_starved(self) -> bool:
        return (
            bool(self.pipelines)
            and len(self.staging_tasks) < self.stage_concurrency
            and self._charge(self.pipelines[0]) > self.budget
        )

    def _io_gate_open(self) -> bool:
        if self.staging_complete:
            return True  # nothing left to prioritize; drain freely
        if self.pipelined:
            if not self.blocked:
                return True
        elif not self.prioritize_staging:
            return True
        # Blocked window (pipelined) / staging-priority mode: open ONLY
        # while staging is budget-starved (requests pending but none
        # runnable) — write completions are the only budget source.
        return bool(self.pipelines and not self.staging_tasks)

    def _probe_may_run(self) -> bool:
        # NEVER inside a pipelined take's blocked window: a probe there
        # would bill its I/O to async_blocked_s — the exact metric
        # async_take exists to minimize and history --check gates.
        # Probes wait for the background drain.
        return self.probe is not None and self.probe.due and not self.blocked

    def _dispatch_io(self) -> None:
        if self._probe_may_run():
            # Park new blob I/O: the in-flight window drains, the loop
            # runs the probe against an idle engine, then reopens.
            return
        if not self._io_gate_open():
            return
        while self.ready_for_io and len(self.io_tasks) < _MAX_IO_CONCURRENCY:
            self.io_tasks.add(
                asyncio.ensure_future(self.ready_for_io.pop(0).write())
            )

    async def _maybe_probe(self) -> None:
        """Run one due probe segment while no blob write is in flight
        (the only moment a probe measures the engine, not contention).
        Called before every I/O dispatch in the pump/drain loops."""
        if self._probe_may_run() and not self.io_tasks:
            await self.probe.run()

    def _update_reporter(self) -> None:
        self.reporter.stage_counts = {
            "ready_for_staging": len(self.pipelines),
            "staging": len(self.staging_tasks),
            "ready_for_io": len(self.ready_for_io),
            "io": len(self.io_tasks),
        }
        self.reporter.budget_remaining = self.budget

    # --- window / stall bookkeeping ------------------------------------

    def _note_stall(self) -> None:
        # Budget-stall EPISODES, not wait iterations: one span
        # per contiguous window in which the head request cannot be
        # admitted, however many task completions the window spans.
        if self._staging_budget_starved():
            if self._stall_start is None:
                self._stall_start = (
                    self.tele.now() if self.tele is not None else 0.0
                )
        elif self._stall_start is not None:
            if self.tele is not None:
                self.tele.record_span(
                    "budget_wait",
                    self._stall_start,
                    self.tele.now() - self._stall_start,
                )
            self._stall_start = None

    def _on_staged(self, pipeline: "_WritePipeline") -> None:
        self.staged_cost_total += pipeline.staging_cost
        if pipeline.in_window:
            self.window_staged_cost += pipeline.staging_cost
        if not self.pipelined:
            return
        self._window_accum += pipeline.staging_cost
        if self._window_accum >= self.memory_budget_bytes:
            self._close_window()

    def _close_window(self) -> None:
        """Record one per-window ``stage_window`` span (the blocked
        window is window 0 — measurable on its own in the trace)."""
        if self._window_accum <= 0:
            return
        if self.tele is not None:
            now = self.tele.now()
            self.tele.record_span(
                "stage_window",
                self._window_start,
                now - self._window_start,
                window=self._window_index,
                bytes=self._window_accum,
            )
            self._window_start = now
        self._window_index += 1
        self._window_accum = 0

    def _first_window_done(self) -> bool:
        return (
            not self.eager_pending
            and self.window_staged_cost >= self.first_window_target
        )

    def _finish_staging(self) -> None:
        if self.staging_complete:
            return
        self.staging_complete = True
        self.reporter.mark_staging_complete()
        if self.tele is not None:
            # The episode open at staging's end; empty where none is, so
            # that a take which never waited for its budget says so (a
            # reader then reads 0, where no span at all reads as "not
            # recorded").
            now = self.tele.now()
            start = now if self._stall_start is None else self._stall_start
            self.tele.record_span("budget_wait", start, now - start)
        self._stall_start = None
        if self.pipelined:
            self._close_window()
            self.reporter.stage_windows = max(self._window_index, 1)
        elif self.tele is not None:
            # Interior measurement of the staging window (the "stage"
            # PHASE is recorded by the take around the whole
            # sync_execute call).
            self.tele.record_span(
                "stage_window",
                self._stage_phase_start,
                self.tele.now() - self._stage_phase_start,
                reqs=self.reporter.total_reqs,
            )
        self.staging_done_event.set()

    # --- the loop ------------------------------------------------------

    async def _pump(self, stop_at_first_window: bool) -> None:
        self._loop = asyncio.get_running_loop()
        self._dispatch_staging()
        while self.staging_tasks or self.pipelines:
            if stop_at_first_window and self._first_window_done():
                return
            self._note_stall()
            done, _ = await asyncio.wait(
                self.staging_tasks | self.io_tasks,
                return_when=asyncio.FIRST_COMPLETED,
            )
            for task in done:
                if task in self.staging_tasks:
                    self.staging_tasks.discard(task)
                    pipeline = task.result()  # re-raises staging failure
                    stager = pipeline.write_req.buffer_stager
                    # From here to its write's end the request holds its
                    # staged buffer, which may be smaller than the
                    # staging cost (e.g. cost model overestimates), or
                    # nothing where that buffer is the caller's own host
                    # value (asked again: staging knows what it staged).
                    uncharged = stager_stages_callers_host_value(stager)
                    self._settle(pipeline, 0 if uncharged else pipeline.buf_size)
                    if uncharged and pipeline.buf_size:
                        telemetry.incr(
                            "scheduler.uncharged_bytes",
                            pipeline.buf_size,
                            rec=self.tele,
                        )
                    self.went_cow = self.went_cow or stager_went_cow(stager)
                    self.dtoh_unfetched_bytes -= pipeline.dtoh_unfetched
                    self.eager_pending.discard(id(pipeline))
                    # Heartbeat feed: bytes past the staging stage (the
                    # window async_take blocks training on).
                    telemetry.incr(
                        "scheduler.bytes_staged",
                        pipeline.buf_size,
                        rec=self.tele,
                    )
                    self._on_staged(pipeline)
                    if pipeline.skipped:
                        # Dedup'd against a previous snapshot: no I/O.
                        self.reporter.report_request_done(0)
                    else:
                        self.ready_for_io.append(pipeline)
                elif task in self.io_tasks:
                    self._on_written(task)
            # Staging first: the I/O gate must see the REFILLED staging
            # set, or it opens spuriously in the instant between one
            # stager finishing and the next starting.
            self._dispatch_staging()
            await self._maybe_probe()
            self._dispatch_io()
            self._update_reporter()
        self._finish_staging()

    def _on_written(self, task: asyncio.Task) -> None:
        self.io_tasks.discard(task)
        pipeline = task.result()  # re-raises a write's or a verify's failure
        self._settle(pipeline, 0)
        if self.probe is not None:
            self.probe.note_written(pipeline.buf_size)
        self.reporter.report_request_done(pipeline.buf_size)

    async def _abort(self) -> None:
        self._dtoh_ahead.clear()  # a callback that looks again starts nothing
        await _cancel_and_drain(self.staging_tasks | self.io_tasks)
        self.executor.shutdown(wait=True)
        self.hash_executor.shutdown(wait=True)

    async def run_blocked_window(self) -> None:
        """Advance to the blocked-window boundary: staging-complete
        (sync / staging-priority modes) or first-window-staged
        (pipelined mode). In-flight tasks stay parked on the event loop
        for ``drain`` to resume."""
        try:
            await self._pump(stop_at_first_window=self.pipelined)
        except BaseException:
            await self._abort()
            raise
        self.reporter.mark_blocked_window_done()
        if self.pipelined:
            self.blocked = False  # I/O gate opens for the drain
            if self.staging_tasks:
                # One turn of the loop, so that what was dispatched and
                # not waited for reaches the staging executor now and
                # not when the drain first runs the loop.
                await asyncio.sleep(0)
            if self.tele is not None:
                self.tele.record_span(
                    "stage_blocked",
                    self._stage_phase_start,
                    self.tele.now() - self._stage_phase_start,
                    reqs=self.reporter.total_reqs,
                    staged_cost=self.staged_cost_total,
                )

    async def drain(self) -> None:
        """Resume to completion: residual staging windows (interleaved
        with their writes), then the storage I/O drain."""
        try:
            await self._pump(stop_at_first_window=False)
            while self.io_tasks or self.ready_for_io:
                await self._maybe_probe()
                self._dispatch_io()
                done, _ = await asyncio.wait(
                    self.io_tasks, return_when=asyncio.FIRST_COMPLETED
                )
                for task in done:
                    self._on_written(task)
                self._update_reporter()
            if (
                self.probe is not None
                and self.probe.ran == 0
                and not self.probe._failed
                and self.reporter.bytes_done > 0
            ):
                # A take smaller than the probe interval still gets ONE
                # sample: "every take self-measures its ceiling" must
                # not silently exclude small takes.
                await self.probe.run()
        except BaseException:
            await self._abort()
            raise
        finally:
            self.executor.shutdown(wait=True)
            self.hash_executor.shutdown(wait=True)
        self.drained_event.set()
        self.reporter.summarize()


async def execute_write_reqs(
    write_reqs: List[WriteReq],
    storage: StoragePlugin,
    memory_budget_bytes: int,
    rank: int,
    prioritize_staging: bool = False,
    pipelined_staging: bool = False,
    stage_eagerly: Optional[Callable[[WriteReq], bool]] = None,
) -> PendingIOWork:
    """Run the write engine to its blocked-window boundary and hand the
    rest back as :class:`PendingIOWork` (see :class:`_WriteScheduler`
    for the three modes). ``take`` drains the returned work in the
    foreground; ``async_take`` on a background thread."""
    # Captured once: the drain (PendingIOWork) and late hashing may run
    # on a background thread after a newer take replaced the ambient
    # recorder.
    tele = telemetry.current()
    sched = _WriteScheduler(
        write_reqs,
        storage,
        memory_budget_bytes,
        rank,
        prioritize_staging=prioritize_staging,
        pipelined_staging=pipelined_staging,
        stage_eagerly=stage_eagerly,
        tele=tele,
    )
    await sched.run_blocked_window()
    return PendingIOWork(scheduler=sched)


def sync_execute_write_reqs(
    write_reqs: List[WriteReq],
    storage: StoragePlugin,
    memory_budget_bytes: int,
    rank: int,
    event_loop: asyncio.AbstractEventLoop,
    prioritize_staging: bool = False,
    pipelined_staging: bool = False,
    stage_eagerly: Optional[Callable[[WriteReq], bool]] = None,
) -> PendingIOWork:
    return run_on_loop(
        event_loop,
        execute_write_reqs(
            write_reqs,
            storage,
            memory_budget_bytes,
            rank,
            prioritize_staging=prioritize_staging,
            pipelined_staging=pipelined_staging,
            stage_eagerly=stage_eagerly,
        ),
    )


class _ReadPipeline:
    def __init__(
        self,
        read_req: ReadReq,
        storage: StoragePlugin,
        tele: Optional[telemetry.TakeTelemetry] = None,
        ledger: Optional[access.AccessLedger] = None,
    ) -> None:
        self.read_req = read_req
        self.storage = storage
        self.tele = tele
        self.ledger = ledger
        # In-place reads allocate no full-size scratch buffer (bytes land
        # in the caller-owned restore target), so they are charged only
        # the plugin's transient overhead — the fs engine's per-stream
        # bounce buffers, a cloud plugin's download chunk — instead of
        # the blob size. This is what lets a multi-GB tensor restore in
        # place under a small memory budget without serializing every
        # stream.
        cost = read_req.buffer_consumer.get_consuming_cost_bytes()
        if read_req.into is not None and storage.supports_in_place_reads:
            cost = min(cost, storage.in_place_read_overhead_bytes(cost))
        self.consuming_cost = cost
        self.read_io: Optional[ReadIO] = None
        self.read_nbytes = 0

    def _read_nbytes(self) -> int:
        br = self.read_req.byte_range
        if br is not None:
            return int(br[1] - br[0])
        if self.read_io is not None and self.read_io.buf is not None:
            try:
                return self.read_io.buf.getbuffer().nbytes
            except Exception:
                pass
        return self.consuming_cost

    async def read(self) -> "_ReadPipeline":
        self.read_io = ReadIO(
            path=self.read_req.path,
            byte_range=self.read_req.byte_range,
            into=self.read_req.into,
            want_crc=self.read_req.want_crc,
            expected_nbytes=self.read_req.expected_nbytes,
        )
        with telemetry.span(
            "storage_read", kind=telemetry.WAIT, rec=self.tele, path=self.read_req.path
        ) as sp:
            await self.storage.read(self.read_io)
            nbytes = sp.attrs["bytes"] = self._read_nbytes()
        self.read_nbytes = nbytes
        telemetry.incr("storage.bytes_read", nbytes, rec=self.tele)
        self._record_access(nbytes)
        return self

    def _record_access(self, nbytes: int) -> None:
        """Attribute this physical read to the manifest leaf (or, for a
        batcher-merged spanning read, each member leaf) in the ambient
        access ledger. Plugins that redirected the read stamped the
        source tier on the ReadIO."""
        ledger = self.ledger
        if ledger is None:
            return
        rr = self.read_req
        source = self.read_io.source if self.read_io is not None else None
        if rr.access_parts:
            for lp, start, end in rr.access_parts:
                ledger.record(
                    lp, rr.path, start, end, end - start, source
                )
            return
        if not rr.logical_path:
            return
        start, end = rr.byte_range if rr.byte_range else (0, nbytes)
        ledger.record(
            rr.logical_path, rr.path, start, end, nbytes, source
        )

    async def consume(self, executor: ThreadPoolExecutor) -> "_ReadPipeline":
        # "consume" covers deserialize + the copy/`device_put` into the
        # restore target (the HtoD leg for jax targets), and the wait
        # for a consume thread; the consumers' own `consume.queued`,
        # `decode` and `htod` spans tell those apart.
        with telemetry.span(
            "consume",
            kind=telemetry.WAIT,
            rec=self.tele,
            path=self.read_req.path,
            bytes=self.consuming_cost,
        ):
            await self.read_req.buffer_consumer.consume_read_io(
                self.read_io, executor
            )
        self.read_io = None  # release
        return self


async def execute_read_reqs(
    read_reqs: List[ReadReq],
    storage: StoragePlugin,
    memory_budget_bytes: int,
    rank: int,
) -> None:
    executor = ThreadPoolExecutor(
        max_workers=_MAX_CPU_CONCURRENCY, thread_name_prefix="tpusnap-consume"
    )
    # Its threads are started here, with no read under way yet: the first
    # consume is submitted from this thread beside the reads that follow
    # it, where a thread's start costs what a read's hand-off must not
    # (PERF.md 6, PR 44).
    start_all_workers(executor)
    reporter = _Reporter(rank=rank, verb="read", total_reqs=len(read_reqs))
    # Ambient recorder (the restore path installs one thread-locally);
    # None for uninstrumented callers (verify's own engine, read_object
    # outside a recorder) — spans then skip, counters stay global.
    tele = telemetry.current()
    # Ambient access ledger (same pattern): installed by the restore /
    # read_object scopes; None means attribution is off for this call.
    ledger = access.current()
    pipelines = deque(
        sorted(
            (_ReadPipeline(rr, storage, tele, ledger) for rr in read_reqs),
            key=lambda p: p.consuming_cost,
            reverse=True,
        )
    )
    budget = memory_budget_bytes
    read_tasks: Set[asyncio.Task] = set()
    consume_tasks: Set[asyncio.Task] = set()
    # In-restore roofline probes (TPUSNAP_PROBE=1): the same runner the
    # write scheduler uses — a probe segment writes its own scratch
    # streams under .tpusnap/probe/ and times both legs, so the READ leg
    # measured through this restore's composed plugin stack becomes the
    # ceiling `restore_roofline_fraction` divides by. Cadence counts
    # payload bytes READ; a probe never overlaps blob reads (dispatch
    # parks while one is due) and never consumes memory budget.
    from .knobs import is_probe_enabled

    probe = (
        _ProbeRunner(storage, rank, tele)
        if tele is not None and tele.enabled and is_probe_enabled()
        else None
    )

    # NOTE on destination prefaulting: a background thread first-touching
    # not-yet-dispatched ``into`` buffers (overlapping page faults with
    # the reads) was tried and MEASURED A LOSS on the 1-vCPU dev host
    # (20 GB restore: 88 s with, 55 s without) — the toucher competes for
    # the one core the bounce copies and fused CRCs run on, and its zero
    # writes evict cache the reads want. Multi-core hosts may differ;
    # revisit with real TPU-VM cores.

    def dispatch_reads() -> None:
        nonlocal budget
        while pipelines and len(read_tasks) < _MAX_IO_CONCURRENCY:
            if probe is not None and probe.due:
                # Park new reads until the in-flight window drains and
                # the probe runs: probe traffic sharing the pipe with
                # blob reads would corrupt both the sample and the
                # storage_read spans analyze attributes.
                break
            head = pipelines[0]
            in_flight = read_tasks or consume_tasks
            if head.consuming_cost > budget and in_flight:
                break
            pipelines.popleft()
            budget -= head.consuming_cost
            read_tasks.add(asyncio.ensure_future(head.read()))

    reporter.total_budget = memory_budget_bytes
    try:
        dispatch_reads()
        while read_tasks or consume_tasks or pipelines:
            done, _ = await asyncio.wait(
                read_tasks | consume_tasks, return_when=asyncio.FIRST_COMPLETED
            )
            for task in done:
                if task in read_tasks:
                    read_tasks.discard(task)
                    pipeline = task.result()
                    if probe is not None:
                        probe.note_written(pipeline.read_nbytes)
                    consume_tasks.add(
                        asyncio.ensure_future(pipeline.consume(executor))
                    )
                elif task in consume_tasks:
                    consume_tasks.discard(task)
                    pipeline = task.result()
                    budget += pipeline.consuming_cost
                    reporter.report_request_done(pipeline.consuming_cost)
            if probe is not None and probe.due and not read_tasks:
                # The read window drained (consumes may still run —
                # they are CPU-side and don't touch the pipe being
                # measured); take the sample, then dispatch resumes.
                await probe.run()
            dispatch_reads()
            reporter.stage_counts = {
                "ready_for_read": len(pipelines),
                "read": len(read_tasks),
                "consume": len(consume_tasks),
            }
            reporter.budget_remaining = budget
        if (
            probe is not None
            and probe.ran == 0
            and not probe._failed
            and reporter.bytes_done > 0
        ):
            # Restore smaller than the probe interval: still measure
            # once, so no probe-enabled restore is fraction-less.
            await probe.run()
    except BaseException:
        # Mirror the write path: a failed request (e.g. checksum
        # mismatch) must not abandon in-flight tasks — orphans would be
        # resumed by the NEXT run_until_complete on a reused event loop
        # and write into a previous call's caller-owned buffers.
        await _cancel_and_drain(read_tasks | consume_tasks)
        # Task cancellation does not interrupt run_in_executor work: a
        # plugin thread may still be mid-write into a caller-owned
        # in-place destination. Wait it out (off-loop) before the error
        # reaches the caller.
        await asyncio.get_running_loop().run_in_executor(
            None, storage.drain_in_flight
        )
        raise
    finally:
        executor.shutdown(wait=True)
    reporter.summarize()


def sync_execute_read_reqs(
    read_reqs: List[ReadReq],
    storage: StoragePlugin,
    memory_budget_bytes: int,
    rank: int,
    event_loop: asyncio.AbstractEventLoop,
) -> None:
    run_on_loop(
        event_loop,
        execute_read_reqs(read_reqs, storage, memory_budget_bytes, rank),
    )
