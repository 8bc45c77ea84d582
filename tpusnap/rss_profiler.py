"""RSS profiler — validate that the memory-budget-gated pipeline holds.

Counterpart of /root/reference/torchsnapshot/rss_profiler.py:32-56: a
background thread samples the process RSS delta on an interval inside a
context manager; benchmarks assert the peak delta stays within the
configured memory budget. :class:`RSSSampler` is the start/stop form
the telemetry subsystem embeds so every take's summary carries its
peak-RSS figure. A take that is watched (``telemetry.HolderWatch``: a
``MetricsSink`` was registered when it began) lends the same thread to
the watch, which ticks faster than the RSS reading does; RSS is read
every ``interval_sec`` either way, and no second thread is started.
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from typing import Any, Generator, List, Optional

import psutil

_DEFAULT_INTERVAL_SEC = 0.1


class RSSSampler:
    """Background-thread RSS-delta sampler with explicit start/stop.

    Samples ``process RSS - baseline`` into ``deltas`` every
    ``interval_sec`` between :meth:`start` and :meth:`stop`; ``stop``
    always appends one final sample, so even a context shorter than the
    interval records a delta. ``stop`` is idempotent and joins the
    thread (no samples land after it returns).

    ``rider`` rides the sampling thread: while its ``period_s`` is a
    number the thread wakes that often and calls ``rider.tick(now,
    late_s)`` (``late_s``: how far the wake-up overshot the period); RSS
    is still read every ``interval_sec``. While ``period_s`` is None the
    thread sleeps ``interval_sec`` at a time, as it does with no rider;
    :meth:`poke` wakes it to read a changed period. ``rider.end()`` runs
    on the thread as it exits."""

    def __init__(
        self,
        deltas: Optional[List[int]] = None,
        interval_sec: float = _DEFAULT_INTERVAL_SEC,
        rider: Any = None,
    ) -> None:
        self.deltas: List[int] = deltas if deltas is not None else []
        self.interval_sec = interval_sec
        self._process = psutil.Process()
        self._baseline = 0
        self._stop = threading.Event()
        self._poked = threading.Event()  # set with _stop, and by poke()
        self._rider = rider
        self._thread: Optional[threading.Thread] = None

    def start(self) -> "RSSSampler":
        if self._thread is not None:
            raise RuntimeError("RSSSampler already started")
        self._baseline = self._process.memory_info().rss
        self._stop.clear()
        self._poked.clear()
        self._thread = threading.Thread(
            target=self._sample_loop if self._rider is None else self._ridden_loop,
            name="tpusnap-rss",
            daemon=True,
        )
        self._thread.start()
        return self

    def sample(self) -> None:
        """Read one delta now."""
        self.deltas.append(self._process.memory_info().rss - self._baseline)

    def _sample_loop(self) -> None:
        # Event.wait doubles as the interval sleep AND the prompt-stop
        # signal: a stop() mid-interval returns immediately instead of
        # holding the caller for a full sleep.
        while not self._stop.wait(self.interval_sec):
            self.sample()

    def _ridden_loop(self) -> None:
        rider = self._rider
        next_rss = time.monotonic() + self.interval_sec
        try:
            while True:
                # Cleared before the period is read: a poke that lands
                # after this line ends the wait below at once.
                self._poked.clear()
                if self._stop.is_set():
                    return
                period = rider.period_s
                slept_at = time.monotonic()
                self._poked.wait(
                    max(next_rss - slept_at, 0.0) if period is None else period
                )
                if self._stop.is_set():
                    return
                now = time.monotonic()
                if period is not None:
                    rider.tick(now, max(now - slept_at - period, 0.0))
                if now >= next_rss:
                    self.sample()
                    # From the read's end, as the plain loop counts it: a
                    # read that is slow (a kernel whose memory lock is
                    # contended) does not take the rider's ticks with it.
                    next_rss = time.monotonic() + self.interval_sec
        finally:
            rider.end()

    def poke(self) -> None:
        """Wake the thread so that it reads the rider's period again."""
        self._poked.set()

    def stop(self) -> List[int]:
        if self._thread is not None:
            self._stop.set()
            self._poked.set()
            self._thread.join()
            self._thread = None
            # Final delta: a sub-interval context still records one.
            self.sample()
        return self.deltas

    @property
    def peak_delta(self) -> int:
        return max(self.deltas, default=0)


@contextmanager
def measure_rss_deltas(
    rss_deltas: List[int], interval_sec: float = _DEFAULT_INTERVAL_SEC
) -> Generator[None, None, None]:
    """Append RSS deltas (bytes, relative to entry) to ``rss_deltas`` every
    ``interval_sec`` until the context exits (reference rss_profiler.py:33-56).
    """
    sampler = RSSSampler(deltas=rss_deltas, interval_sec=interval_sec)
    sampler.start()
    try:
        yield
    finally:
        sampler.stop()
