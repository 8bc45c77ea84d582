"""Per-take telemetry: stage spans, rank counters, persisted traces.

The paper's core claims — overlapped DtoH and storage I/O, memory-budget
driven scheduling, write load spread across ranks — are only verifiable
if a running take can say *where* its wall-clock and budget went, per
rank. This module is that instrument:

- **Spans** — monotonic-clock intervals recorded around every pipeline
  stage (flatten, the G1 plan gather, prepare, staging, checksum
  passes, storage writes, budget waits, barriers/KV waits). Span
  capture is gated by the ``TPUSNAP_TELEMETRY`` knob (on by default;
  the disabled path is a single dict lookup + ``None`` check, and
  starts no thread).
- **Background threads of a take or restore whose spans are on** — two,
  and no more whatever is registered: ``tpusnap-rss`` (this module's
  :class:`~tpusnap.rss_profiler.RSSSampler`, the process's RSS every
  100 ms) and, in a take, ``tpusnap-progress`` (:mod:`tpusnap.progress`,
  the heartbeat, at ``TPUSNAP_HEARTBEAT_S``). Where a ``MetricsSink``
  was registered when the operation began, ``tpusnap-rss`` also carries
  the :class:`HolderWatch` and wakes every 5 ms from ``async_take``'s
  return (a restore's start) to the operation's end; RSS is read every
  100 ms still.
- **The holder** — what kept the two threads tpusnap does not own the
  time of from running: the thread that called ``async_take`` /
  ``restore`` and the thread that runs the operation's event loop,
  sampled (:class:`HolderWatch`: ``caller.<class>`` / ``loop.<class>``
  spans, the ``caller.*`` / ``loop.*`` / ``watch.*`` counters), and the
  loop's lateness per request, measured (``<name>.resumed``, see
  :func:`run_handoff`).
- **Counters** — atomic, ALWAYS-ON (knob-independent): retry attempts
  per classification, injected faults, staging-pool hits, bytes
  written, dedup skips. Cheap enough for the hot path (one lock'd
  ``dict`` add).
- **Gauges** — high-water marks (scheduler budget in use, peak RSS
  delta sampled by :mod:`tpusnap.rss_profiler`).
- **I/O histograms** — always-on log2-bucketed latency × size
  histograms per ``(op, plugin class)`` at the storage-plugin boundary
  (:class:`LogHistogram`/:class:`IOStats`, fed by the registry's
  instrumentation wrapper): p50/p95/p99/max derivable from any
  cross-rank merge, recorded per rank and folded into the rollup —
  whole-op spans hide tail latency; these are where it lives.
- **Roofline probes** — opt-in (``TPUSNAP_PROBE=1``) in-take probe
  segments the write scheduler interleaves between I/O windows; their
  samples land here and the summary derives a drift-immune
  ``roofline_fraction`` (see :mod:`tpusnap.analyze`).
- **TakeTelemetry** — the per-take aggregate. One is installed
  process-globally for the duration of a take (background drain
  threads re-install it thread-locally via :func:`use`); module-level
  :func:`span`/:func:`incr`/:func:`event` record into it from any
  layer without threading a handle through every call.

Persistence: each rank serializes its trace to **Chrome trace-event
JSON** (load it in ``chrome://tracing`` / Perfetto) plus a compact
summary, stored inside the snapshot at
``.tpusnap/telemetry/rank_<k>.json`` — written after the rank's blob
writes drain and BEFORE the metadata commit, so the
metadata-written-last invariant holds (a trace file can be orphaned by
an abort; a committed snapshot missing its trace only means telemetry
was disabled or its best-effort write failed). Rank 0 additionally
folds a cross-rank rollup (per-stage p50/max, bytes written, retries,
budget high-water) into the take's metadata ``extras`` — surfaced by
``python -m tpusnap trace <path>``.

External collectors subscribe through :class:`MetricsSink`
(``register_metrics_sink``): per-span and per-counter callbacks plus
one take-summary callback. Sink exceptions are swallowed — telemetry
must never fail a take.
"""

from __future__ import annotations

import asyncio
import contextvars
import itertools
import json
import logging
import math
import os
import sys
import threading
import time
from contextlib import contextmanager, nullcontext
from typing import (
    Any,
    Callable,
    Dict,
    Generator,
    List,
    NamedTuple,
    Optional,
    Tuple,
    Union,
)

from .knobs import is_telemetry_enabled

logger = logging.getLogger(__name__)

from .io_types import TELEMETRY_DIR  # canonical sidecar path (io_types)
from . import flight as _flight  # black-box event feed (span open/close)

# Wall-clock seam: timestamps only (started_at); ALL duration math in
# this file is monotonic — direct wall-clock CALLS are lint-forbidden
# here (tests/test_knob_docs.py enforces the invariant); only this bare
# reference is allowed.
_wall = time.time

# Summary of the most recent completed take in this process (set by
# end_take); benchmarks read this to embed the stage breakdown in their
# JSON without re-reading the snapshot.
LAST_TAKE_SUMMARY: Optional[Dict[str, Any]] = None

# Summary of the most recent completed restore in this process (set by
# Snapshot._restore_locked) — the restore-path counterpart benchmarks
# read for their restore stage_breakdown.
LAST_RESTORE_SUMMARY: Optional[Dict[str, Any]] = None


def telemetry_rank_path(rank: int) -> str:
    """Storage-relative path of one rank's persisted trace."""
    return f"{TELEMETRY_DIR}/rank_{rank}.json"


# -------------------------------------------------------------- records

# What a span says about the thread that recorded it.
PHASE = "phase"  # one step of the linear pipeline; phases tile a thread
WAIT = "wait"  # nothing ran on the recording thread's behalf: a queue, an
#                await seen from the event loop, a barrier, a transfer
#                that was started earlier
WORK = "work"  # the body ran on the recording thread, start to end


class SpanRecord(NamedTuple):
    """One span as the seam keeps, persists and publishes it. ``start``
    and ``end`` are absolute ``time.monotonic()`` readings, so records
    of different takes, restores and the caller's own timestamps share
    one clock. ``op`` names the take or restore the span belongs to;
    ``parent`` is the ``id`` of the span that caused it (the enclosing
    span on that thread, or the request's span for work handed to
    another thread), None at the top."""

    id: int
    name: str
    start: float
    end: float
    thread: str
    kind: str
    op: str
    parent: Optional[int]
    attrs: Dict[str, Any]

    @property
    def duration_s(self) -> float:
        return self.end - self.start


# Span ids and op numbers are unique in the process, so a parent id can
# never resolve into another take's spans.
_span_ids = itertools.count(1)
_op_numbers = itertools.count(1)

# The innermost open span of the running thread or asyncio task, as
# (recorder, span id): what a new span takes as its parent. asyncio
# tasks copy it at creation, so a request's task inherits the phase
# that created it; a thread starts with none.
_ambient: "contextvars.ContextVar[Optional[Tuple[TakeTelemetry, int]]]" = (
    contextvars.ContextVar("tpusnap_ambient_span", default=None)
)


class OpenSpan:
    """Handle of a span that is still open: its ``id`` (what children
    name as their parent) and its ``attrs``, which the body may add to
    before the span closes; ``end`` is the record's own end once it has
    closed."""

    __slots__ = ("id", "attrs", "end")

    def __init__(self, span_id: int, attrs: Dict[str, Any]) -> None:
        self.id = span_id
        self.attrs = attrs
        self.end: Optional[float] = None


_trace_me: Any = None  # jax.profiler.TraceAnnotation, False if unavailable


def _annotate(name: str, op: str, prefix: str = "tpusnap:") -> Any:
    """Open ``tpusnap:<name>`` in the profiler's own trace (a TraceMe on
    the calling thread) and return it, or None without jax. With no
    profile running this is one atomic load inside TraceMe (~0.6 us)."""
    global _trace_me
    if _trace_me is None:
        try:
            from jax.profiler import TraceAnnotation

            _trace_me = TraceAnnotation
        except Exception:
            _trace_me = False
    if not _trace_me:
        return None
    ann = _trace_me(f"{prefix}{name}", op=op)
    ann.__enter__()
    return ann


# --------------------------------------------------------------- sinks


class MetricsSink:
    """Subscriber interface for external collectors. Override any
    subset; default implementations are no-ops. Callbacks run inline on
    the recording thread and must be fast and non-raising (raises are
    swallowed, but the time is still yours)."""

    def on_span(self, name: str, duration_s: float, attrs: Dict[str, Any]) -> None:
        pass

    def on_span_record(self, record: SpanRecord) -> None:
        """The whole record of a span (start, end, thread, kind, op,
        parent). A sink that defines only ``on_span`` gets every span
        through it, as before."""
        self.on_span(record.name, record.duration_s, record.attrs)

    def on_counter(self, name: str, delta: int, value: int) -> None:
        pass

    def on_take_summary(self, summary: Dict[str, Any]) -> None:
        pass

    def on_restore_summary(self, summary: Dict[str, Any]) -> None:
        pass

    def on_slo_update(self, state: Dict[str, Any]) -> None:
        """Checkpoint-SLO state refresh (:mod:`tpusnap.slo`): RPO,
        data-at-risk, estimated RTO, commit interval — pushed at
        heartbeat cadence while a take runs and at every commit."""
        pass

    def on_tier_update(self, state: Dict[str, Any]) -> None:
        """Write-back tier status refresh (:mod:`tpusnap.tiering`):
        uploader state, upload lag bytes/seconds, degraded flag —
        pushed by the background drain on every state transition and
        blob completion."""
        pass


_sinks: Tuple[MetricsSink, ...] = ()
# Beside each sink, the callback a span goes to, looked up once when the
# sink is registered: ``on_span_record`` where its class defines one.
_span_sinks: Tuple[Tuple[MetricsSink, str], ...] = ()
_sinks_lock = threading.Lock()
# (sink class name, callback name) pairs already warned about since the
# last take/restore began — a broken exporter logs ONE rate-limited
# WARNING per sink class per callback per take instead of being
# silently invisible (or spamming once per span).
_sink_warned: set = set()


def _reset_sink_warnings() -> None:
    with _sinks_lock:
        _sink_warned.clear()


def _span_callback(sink: MetricsSink) -> str:
    """A sink whose class defines ``on_span_record`` gets the record;
    any other (a ``MetricsSink`` that overrides only ``on_span``, or a
    duck-typed sink written before records existed) gets ``on_span``."""
    handler = getattr(type(sink), "on_span_record", None)
    if handler is None or handler is MetricsSink.on_span_record:
        return "on_span"
    return "on_span_record"


def register_metrics_sink(sink: MetricsSink) -> None:
    global _sinks, _span_sinks
    with _sinks_lock:
        _sinks = _sinks + (sink,)
        _span_sinks = _span_sinks + ((sink, _span_callback(sink)),)


def unregister_metrics_sink(sink: MetricsSink) -> None:
    global _sinks, _span_sinks
    with _sinks_lock:
        _sinks = tuple(s for s in _sinks if s is not sink)
        _span_sinks = tuple(p for p in _span_sinks if p[0] is not sink)


@contextmanager
def metrics_sink(sink: MetricsSink) -> Generator[MetricsSink, None, None]:
    """Scoped registration: ``with metrics_sink(MySink()) as s: ...``
    unregisters on exit even when the body raises — a failing test (or a
    short-lived collector) can no longer leak its sink into the
    process-global tuple."""
    register_metrics_sink(sink)
    try:
        yield sink
    finally:
        unregister_metrics_sink(sink)


def _notify(method: str, *args) -> None:
    for sink in _sinks:
        _notify_one(sink, method, *args)


def _notify_one(sink: MetricsSink, method: str, *args) -> None:
    try:
        getattr(sink, method)(*args)
    except Exception:
        # Swallowed (telemetry never fails a take) but NOT silent: a
        # broken exporter is diagnosable from one WARNING naming the
        # sink class and callback, rate-limited to once per sink
        # class per callback per take.
        key = (type(sink).__name__, method)
        with _sinks_lock:
            first = key not in _sink_warned
            _sink_warned.add(key)
        if first:
            logger.warning(
                "MetricsSink %s.%s raised; exception swallowed "
                "(telemetry never fails a take) — further failures "
                "from this sink/callback suppressed until the next "
                "take",
                key[0],
                method,
                exc_info=True,
            )


def _notify_span(record: SpanRecord) -> None:
    for sink, callback in _span_sinks:
        if callback == "on_span":
            _notify_one(
                sink, "on_span", record.name, record.duration_s, record.attrs
            )
        else:
            _notify_one(sink, "on_span_record", record)


def notify_slo_update(state: Dict[str, Any]) -> None:
    """Fan one SLO state refresh out to every registered sink (the
    :mod:`tpusnap.slo` publisher's sink leg; same swallow/rate-limit
    contract as every other callback)."""
    _notify("on_slo_update", state)


def notify_tier_update(state: Dict[str, Any]) -> None:
    """Fan one write-back tier status refresh out to every registered
    sink (the :mod:`tpusnap.tiering` uploader's sink leg)."""
    _notify("on_tier_update", state)


# ---------------------------------------------------- global counters

# Process-lifetime counters, knob-independent: retry/fault/pool events
# are recorded here even outside a take, so tests and sinks can observe
# them without a snapshot in flight.
_global_counters: Dict[str, int] = {}
_counters_lock = threading.Lock()


def counter_value(name: str) -> int:
    with _counters_lock:
        return _global_counters.get(name, 0)


def global_counters_snapshot() -> Dict[str, int]:
    """Copy of the process-lifetime counters — the monotonic domain the
    Prometheus textfile sink exports (take-local counters reset per
    take and would break ``rate()``)."""
    with _counters_lock:
        return dict(_global_counters)


def reset_global_counters() -> None:
    """Test aid; production code never resets."""
    with _counters_lock:
        _global_counters.clear()


# ----------------------------------------------------- I/O histograms

# Bucket key for non-positive observations (a zero-latency op, an empty
# write): kept separate so quantile math never takes log2(0).
_ZERO_BUCKET = -1074  # below the smallest positive float64 exponent


class LogHistogram:
    """log2-bucketed histogram: observation ``v`` lands in bucket
    ``floor(log2 v)`` (i.e. the half-open interval ``[2^k, 2^(k+1))``),
    so the whole dynamic range of I/O latencies (microseconds to
    minutes) and sizes (bytes to gigabytes) fits in a few dozen integer
    buckets with bounded relative error. Tracks exact count/sum/min/max
    alongside, so ``quantile(1.0)`` is the true max and single-sample
    histograms are exact. Mergeable across ranks (bucket-count sums) —
    the property the cross-rank rollup and the trend gates rely on;
    p50/p95/p99 are derivable from any merge. NOT thread-safe on its
    own; callers hold their registry lock."""

    __slots__ = ("buckets", "count", "total", "vmin", "vmax")

    def __init__(self) -> None:
        self.buckets: Dict[int, int] = {}
        self.count = 0
        self.total = 0.0
        self.vmin = math.inf
        self.vmax = 0.0

    def observe(self, value: float) -> None:
        v = float(value)
        if v > 0.0:
            # floor(log2 v) == frexp exponent - 1 (v = m * 2^e, m in
            # [0.5, 1)) — no log call, exact at bucket boundaries.
            k = math.frexp(v)[1] - 1
        else:
            v = 0.0
            k = _ZERO_BUCKET
        self.buckets[k] = self.buckets.get(k, 0) + 1
        self.count += 1
        self.total += v
        if v < self.vmin:
            self.vmin = v
        if v > self.vmax:
            self.vmax = v

    def quantile(self, q: float) -> Optional[float]:
        """Value at quantile ``q`` in [0, 1]: geometrically interpolated
        within the bucket holding the q-th observation (rank position
        maps to an exponent fraction, so the estimate moves CONTINUOUSLY
        as mass shifts across a bucket boundary — a gated p99 must not
        jump 2x when the true latency drifts 10% across a power of
        two), clamped into the exact observed [min, max]. Exact for max
        and for single-sample histograms (a lone sample interpolates to
        its bucket's upper edge, which the clamp pins to the sample)."""
        if self.count == 0:
            return None
        if q >= 1.0:
            return self.vmax
        target = q * self.count
        cum = 0
        for k in sorted(self.buckets):
            n = self.buckets[k]
            cum += n
            if cum >= target:
                if k == _ZERO_BUCKET:
                    return 0.0
                frac = (target - (cum - n)) / n
                est = math.ldexp(1.0, k) * (2.0 ** frac)
                return max(min(est, self.vmax), self.vmin)
        return self.vmax

    def merge(self, other: "LogHistogram") -> None:
        for k, n in other.buckets.items():
            self.buckets[k] = self.buckets.get(k, 0) + n
        self.count += other.count
        self.total += other.total
        self.vmin = min(self.vmin, other.vmin)
        self.vmax = max(self.vmax, other.vmax)

    def to_dict(self) -> Dict[str, Any]:
        return {
            "count": self.count,
            "total": self.total,
            "min": self.vmin if self.count else None,
            "max": self.vmax,
            "buckets": {str(k): n for k, n in sorted(self.buckets.items())},
        }

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "LogHistogram":
        h = cls()
        for k, n in (d.get("buckets") or {}).items():
            h.buckets[int(k)] = int(n)
        h.count = int(d.get("count", 0))
        h.total = float(d.get("total", 0.0))
        h.vmin = float(d["min"]) if d.get("min") is not None else math.inf
        h.vmax = float(d.get("max", 0.0))
        return h


class IOStats:
    """Latency × size histogram pair for one (op, plugin-class) key at
    the storage-plugin boundary: per-op latency in seconds and payload
    size in bytes, each log2-bucketed, plus the derived quantiles the
    doctor CLI and the regression gates read."""

    __slots__ = ("latency", "size")

    def __init__(self) -> None:
        self.latency = LogHistogram()
        self.size = LogHistogram()

    def observe(self, seconds: float, nbytes: int) -> None:
        self.latency.observe(seconds)
        self.size.observe(nbytes)

    def merge_dict(self, d: Dict[str, Any]) -> None:
        if "latency" in d:
            self.latency.merge(LogHistogram.from_dict(d["latency"]))
        if "size" in d:
            self.size.merge(LogHistogram.from_dict(d["size"]))

    def to_dict(self) -> Dict[str, Any]:
        lat = self.latency
        out: Dict[str, Any] = {
            "count": lat.count,
            "total_s": round(lat.total, 6),
            "bytes_total": int(self.size.total),
            "latency": lat.to_dict(),
            "size": self.size.to_dict(),
        }
        for name, q in (("p50_s", 0.5), ("p95_s", 0.95), ("p99_s", 0.99)):
            v = lat.quantile(q)
            out[name] = round(v, 9) if v is not None else None
        out["max_s"] = round(lat.vmax, 9) if lat.count else None
        return out


# Process-lifetime I/O histograms, knob-independent like the counters:
# one IOStats per "<op>.<PluginClass>" key ("write.FSStoragePlugin").
# The Prometheus sink exports quantiles from THIS registry (stable
# across takes); per-take copies ride TakeTelemetry and the rollup.
_global_io_stats: Dict[str, IOStats] = {}
_io_stats_lock = threading.Lock()


def observe_io(
    op: str,
    plugin: str,
    seconds: float,
    nbytes: int,
    rec: Optional["TakeTelemetry"] = None,
) -> None:
    """Record one storage-plugin op (write/read/delete/list) into the
    process-global histograms AND the in-flight take/restore recorder
    (the ambient one, or an explicit ``rec``). Always-on: the cost is
    two dict updates per multi-MB I/O op."""
    key = f"{op}.{plugin}"
    with _io_stats_lock:
        st = _global_io_stats.get(key)
        if st is None:
            st = _global_io_stats[key] = IOStats()
        st.observe(seconds, nbytes)
    rec = rec if rec is not None else current()
    if rec is not None:
        rec.observe_io(key, seconds, nbytes)


def global_io_histograms_snapshot() -> Dict[str, Dict[str, Any]]:
    """Serialized copy of the process-lifetime I/O histograms (the
    monotonic domain the Prometheus sink exports quantiles from)."""
    with _io_stats_lock:
        return {k: v.to_dict() for k, v in sorted(_global_io_stats.items())}


def reset_global_io_histograms() -> None:
    """Test aid; production code never resets."""
    with _io_stats_lock:
        _global_io_stats.clear()


def probe_aggregate(samples: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Fold in-take roofline probe samples into the compact aggregate
    the summary/rollup/history carry: sample count, p50 of the per-probe
    write/read ceilings, total probe bytes and elapsed time."""

    def _p50(key: str) -> Optional[float]:
        vals = sorted(s[key] for s in samples if s.get(key))
        return round(vals[len(vals) // 2], 4) if vals else None

    return {
        "probes": len(samples),
        "write_gbps_p50": _p50("write_gbps"),
        "read_gbps_p50": _p50("read_gbps"),
        "bytes": int(sum(s.get("bytes", 0) for s in samples)),
        "elapsed_s": round(sum(s.get("elapsed_s", 0.0) for s in samples), 6),
    }


def merge_io_histograms(
    dicts: List[Dict[str, Dict[str, Any]]],
) -> Dict[str, Dict[str, Any]]:
    """Merge serialized per-rank ``io_histograms`` maps (bucket-count
    sums per key) — the cross-rank rollup's histogram fold. Quantiles
    are recomputed from the merged buckets."""
    merged: Dict[str, IOStats] = {}
    for d in dicts:
        for key, st_dict in (d or {}).items():
            st = merged.get(key)
            if st is None:
                st = merged[key] = IOStats()
            try:
                st.merge_dict(st_dict)
            except Exception:
                continue
    return {k: v.to_dict() for k, v in sorted(merged.items())}


# ------------------------------------------------------- TakeTelemetry


class TakeTelemetry:
    """Thread-safe per-take aggregate of spans, counters and gauges.

    ``enabled`` gates SPAN capture only (the TPUSNAP_TELEMETRY knob,
    sampled once at construction so a take is internally consistent);
    counters and gauges are always recorded. Spans are kept as
    :class:`SpanRecord` on the absolute monotonic clock; ``now()`` and
    the persisted trace's ``ts`` are offsets from ``t0``, the take's
    start on that clock."""

    def __init__(
        self,
        rank: int,
        enabled: Optional[bool] = None,
        kind: str = "take",
        caller: Optional[Tuple[int, int, Optional[int]]] = None,
    ) -> None:
        """``caller`` is the :func:`thread_key` of the thread whose call
        this operation is, where that is not the constructing thread
        (``async_restore``'s)."""
        self.rank = rank
        self.enabled = is_telemetry_enabled() if enabled is None else enabled
        # One identifier for every span of this take or restore.
        self.op = f"{kind}-{next(_op_numbers)}"
        self.t0 = time.monotonic()
        self.wall0 = _wall()
        # Identity/outcome context merged into summary(): the take path
        # sets kind/take_id/path/world_size once they're agreed, and
        # completed=True strictly after the commit — the history store
        # and export sinks key off these (an aborted take must not
        # become a throughput trend point).
        self.meta: Dict[str, Any] = {}
        self._lock = threading.Lock()
        self._spans: List[SpanRecord] = []
        # The annotation a PhaseMarker holds open in the profiler's
        # trace; finalize() closes one that a failed take left open.
        self._phase_annotation: Any = None
        # (name, ts_s, thread_name, attrs) — instant events (faults, retries)
        self._events: List[Tuple[str, float, str, Dict[str, Any]]] = []
        self._counters: Dict[str, int] = {}
        self._gauges: Dict[str, float] = {}
        # Per-take I/O histograms ("<op>.<PluginClass>" → IOStats) and
        # in-take roofline probe samples — always-on like the counters.
        self._io_hist: Dict[str, IOStats] = {}
        self._probe_samples: List[Dict[str, Any]] = []
        self._finalized_wall_s: Optional[float] = None
        # Live state for the heartbeat/watchdog (tpusnap.progress):
        # in-flight named ops keyed by an opaque token (an op may span
        # awaits, so a per-thread stack would mis-pop under the event
        # loop's interleaving), plus the most recently COMPLETED phase.
        self._inflight: Dict[object, Tuple[str, str]] = {}
        self._last_phase: Optional[str] = None
        self._rss_sampler = None
        # The holder watch rides the RSS sampler's thread, and only
        # where a sink was registered when the operation began.
        self._watch: Optional[HolderWatch] = None
        if self.enabled:
            try:
                from .rss_profiler import RSSSampler

                if _sinks:
                    self._watch = HolderWatch(self, caller or thread_key())
                self._rss_sampler = RSSSampler(interval_sec=0.1, rider=self._watch)
                self._rss_sampler.start()
            except Exception:
                self._rss_sampler = self._watch = None

    # --- recording ------------------------------------------------------

    def now(self) -> float:
        return time.monotonic() - self.t0

    def ambient_parent(self) -> Optional[int]:
        """The id of the innermost open span of this recorder on the
        running thread or task, or None."""
        ambient = _ambient.get()
        return ambient[1] if ambient is not None and ambient[0] is self else None

    def _keep(self, record: SpanRecord) -> None:
        # No lock: a list's append is atomic, and so is the copy that the
        # readers take under the lock. A lock here would be taken by every
        # recording thread, the event loop's among them, once a span.
        self._spans.append(record)
        _notify_span(record)

    def record_span(
        self,
        name: str,
        start_s: float,
        dur_s: float,
        phase: bool = False,
        *,
        kind: Optional[str] = None,
        span_id: Optional[int] = None,
        **attrs: Any,
    ) -> None:
        """Record a span after the fact: ``start_s`` is an offset from
        ``t0`` as :meth:`now` gives it. Its kind is ``wait`` unless
        said otherwise: a span timed from outside (an await seen from
        the event loop, a window between two marks) holds queueing and
        other threads' work, not the recording thread's."""
        if not self.enabled:
            return
        start = self.t0 + start_s
        self._keep(
            SpanRecord(
                span_id if span_id is not None else next(_span_ids),
                name,
                start,
                start + dur_s,
                threading.current_thread().name,
                PHASE if phase else (kind or WAIT),
                self.op,
                self.ambient_parent(),
                attrs,
            )
        )

    @contextmanager
    def span(
        self,
        name: str,
        phase: bool = False,
        *,
        kind: Optional[str] = None,
        **attrs: Any,
    ) -> Generator[OpenSpan, None, None]:
        """Record a span around the body, on the thread (or asyncio
        task) that runs it; spans opened inside take it as their parent.
        Its kind is ``work`` unless said otherwise. A ``phase`` or
        ``work`` span is also a ``tpusnap:<name>`` annotation in the
        profiler's own trace; a ``wait`` span is not, because the waits
        of one event-loop thread interleave and a trace line nests."""
        if not self.enabled:
            yield OpenSpan(0, attrs)
            return
        kind = PHASE if phase else (kind or WORK)
        parent = self.ambient_parent()
        sp = OpenSpan(next(_span_ids), attrs)
        ambient = _ambient.set((self, sp.id))
        annotation = _annotate(name, self.op) if kind != WAIT else None
        start = time.monotonic()
        token = self.op_enter(name)
        try:
            yield sp
        finally:
            end = sp.end = time.monotonic()
            self.op_exit(token)
            if annotation is not None:
                annotation.__exit__(None, None, None)
            try:
                _ambient.reset(ambient)
            except ValueError:  # closed in another context than it opened in
                _ambient.set(None)
            self._keep(
                SpanRecord(
                    sp.id, name, start, end, threading.current_thread().name,
                    kind, self.op, parent, sp.attrs,
                )
            )

    def handoff(
        self,
        name: str,
        fn: Callable[..., Any],
        work: Union[bool, str] = True,
        ended: Optional[List[float]] = None,
        **attrs: Any,
    ) -> Callable[..., Any]:
        """Wrap ``fn`` for an executor. Call this where the function is
        submitted; the wrapper records on the worker thread
        ``<name>.queued`` (kind ``wait``: from now until a worker picks
        the function up) and, around the body, a span of kind ``work``:
        ``<name>.work``, or the name given as ``work``, or none (False)
        for a body that records its own spans. Both are children of the
        span that is open at the submit (the request's span), and so is
        whatever the body records: the recorder and that parent are
        installed on the worker for the body's length. ``ended``, a list,
        is given the instant the body ended, on the worker: the work
        span's own end (:func:`run_handoff` counts the loop's lateness
        from it)."""
        if not self.enabled:
            return fn
        parent = self.ambient_parent()
        submitted = time.monotonic()
        work_name = f"{name}.work" if work is True else work

        def run(*args: Any, **kwargs: Any) -> Any:
            self._keep(
                SpanRecord(
                    next(_span_ids), f"{name}.queued", submitted, time.monotonic(),
                    threading.current_thread().name, WAIT, self.op, parent, {},
                )
            )
            ambient = _ambient.set((self, parent) if parent is not None else None)
            body = self.span(work_name, **attrs) if work_name else nullcontext()
            sp = None
            try:
                with use(self), body as sp:
                    return fn(*args, **kwargs)
            finally:
                if ended is not None:
                    ended.append(sp.end if sp is not None else time.monotonic())
                _ambient.reset(ambient)

        return run

    # --- live state (heartbeat/watchdog feed) ---------------------------

    def op_enter(self, name: str) -> Optional[object]:
        """Mark a named op as in flight; returns the token to pass back
        to :meth:`op_exit`. No-op (None) when span capture is off."""
        if not self.enabled:
            return None
        token = object()
        thread = threading.current_thread().name
        with self._lock:
            self._inflight[token] = (thread, name)
        # Flight-recorder feed (span OPEN): an op that began but never
        # ended is exactly what the post-mortem timeline must show.
        _flight.record("op_begin", op=name)
        return token

    def op_exit(self, token: Optional[object]) -> None:
        if token is None:
            return
        with self._lock:
            entry = self._inflight.pop(token, None)
        if entry is not None:
            _flight.record("op_end", op=entry[1])

    @contextmanager
    def op(self, name: str) -> Generator[None, None, None]:
        """In-flight tracking only (no span record) — for call sites
        that record their span manually but should still be visible to
        the stall watchdog while blocked."""
        token = self.op_enter(name)
        try:
            yield
        finally:
            self.op_exit(token)

    def note_phase(self, name: str) -> None:
        """Record ``name`` as the most recently completed phase (called
        by :class:`PhaseMarker`); read by the heartbeat publisher."""
        self._last_phase = name
        _flight.record("phase", op=name)

    def live_snapshot(self) -> Dict[str, Any]:
        """One consistent snapshot of the recorder's observable state
        for the progress pump: last completed phase, in-flight ops in
        start order (oldest first), counters, and a monotonically
        growing mark count (spans + events) whose advance IS forward
        progress."""
        with self._lock:
            ops = list(self._inflight.values())
            counters = dict(self._counters)
            marks = len(self._spans) + len(self._events)
            probe_gbps = (
                self._probe_samples[-1].get("write_gbps")
                if self._probe_samples
                else None
            )
        out = {
            "phase": self._last_phase,
            "ops": ops,
            "counters": counters,
            "marks": marks,
        }
        if probe_gbps:
            # Latest in-take probe ceiling: lets the heartbeat/watch
            # table express live MB/s as a fraction of the achievable.
            out["probe_write_gbps"] = round(probe_gbps, 3)
        return out

    def event(self, name: str, **attrs: Any) -> None:
        if not self.enabled:
            return
        thread = threading.current_thread().name
        with self._lock:
            self._events.append((name, self.now(), thread, attrs))

    def incr(self, name: str, n: int = 1) -> None:
        # No sink notification here: the module-level incr() notifies
        # with the PROCESS-GLOBAL cumulative value, so sinks see one
        # consistent monotonic domain instead of take-local resets.
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + n

    def gauge_max(self, name: str, value: float) -> None:
        with self._lock:
            if value > self._gauges.get(name, float("-inf")):
                self._gauges[name] = value

    def observe_io(self, key: str, seconds: float, nbytes: int) -> None:
        """Take-local leg of :func:`observe_io` (always-on)."""
        with self._lock:
            st = self._io_hist.get(key)
            if st is None:
                st = self._io_hist[key] = IOStats()
            st.observe(seconds, nbytes)

    def add_probe_sample(self, sample: Dict[str, Any]) -> None:
        """Record one in-take roofline probe result (scheduler's probe
        runner): ``write_gbps``/``read_gbps``/``bytes``/``elapsed_s``."""
        with self._lock:
            self._probe_samples.append(dict(sample))

    # --- finalization ---------------------------------------------------

    def finalize(self) -> None:
        """Freeze the take wall-clock and stop the RSS sampler (a watched
        take reads its RSS peak here, hands what the watch has seen so
        far to the record, and keeps the thread until :meth:`close`).
        Idempotent; spans recorded after this still reach sinks but are
        not part of the persisted trace's coverage window."""
        if self._finalized_wall_s is not None:
            return
        if self._watch is not None and self._watch.period_s is not None:
            try:
                self._watch.flush()
                self._rss_sampler.sample()
                self.gauge_max(
                    "peak_rss_delta_bytes", float(self._rss_sampler.peak_delta)
                )
            except Exception:
                pass
        self._finalized_wall_s = self.now()
        annotation, self._phase_annotation = self._phase_annotation, None
        if annotation is not None:
            annotation.__exit__(None, None, None)
        ambient = _ambient.get()
        if ambient is not None and ambient[0] is self:
            _ambient.set(None)  # a phase that a failure left begun
        if self._watch is None or self._watch.period_s is None:
            self._stop_sampler()

    def _stop_sampler(self) -> None:
        sampler, self._rss_sampler = self._rss_sampler, None
        if sampler is not None:
            try:
                sampler.stop()  # a watch's last spans land on its thread here
                self.gauge_max("peak_rss_delta_bytes", float(sampler.peak_delta))
            except Exception:
                pass

    def watch_begin(self) -> None:
        """From here to :meth:`close` the watch samples the caller's and
        the loop's thread (``async_take`` calls this as it returns, a
        restore as it starts). Nothing without a watch."""
        if self._watch is not None and self._rss_sampler is not None:
            self._watch.begin()
            self._rss_sampler.poke()

    def note_loop_thread(self) -> None:
        """The calling thread is about to run this operation's event
        loop."""
        if self._watch is not None and (
            self._watch.loop is None or self._watch.loop[0] != threading.get_ident()
        ):
            self._watch.loop = thread_key()

    def close(self) -> None:
        """The operation's end: the end of the sampler's thread, where a
        watch kept it past :meth:`finalize` (its last spans are recorded
        as it ends), and :meth:`finalize`."""
        self._stop_sampler()
        self.finalize()

    @property
    def take_wall_s(self) -> float:
        return (
            self._finalized_wall_s
            if self._finalized_wall_s is not None
            else self.now()
        )

    # --- serialization --------------------------------------------------

    def summary(self) -> Dict[str, Any]:
        """Compact aggregate: per-span-name {count, total_s, p50_s,
        max_s}, phase list (for wall-clock coverage), counters, gauges."""
        with self._lock:
            spans = list(self._spans)
            counters = dict(self._counters)
            gauges = dict(self._gauges)
            events = list(self._events)
            io_hist = {k: v.to_dict() for k, v in sorted(self._io_hist.items())}
            probes = [dict(s) for s in self._probe_samples]
        by_name: Dict[str, List[float]] = {}
        phase_total: Dict[str, float] = {}
        for r in spans:
            by_name.setdefault(r.name, []).append(r.duration_s)
            if r.kind == PHASE:
                phase_total[r.name] = phase_total.get(r.name, 0.0) + r.duration_s
        stages = {}
        for name, durs in sorted(by_name.items()):
            durs_sorted = sorted(durs)
            stages[name] = {
                "count": len(durs),
                "total_s": round(sum(durs), 6),
                "p50_s": round(durs_sorted[len(durs_sorted) // 2], 6),
                "max_s": round(durs_sorted[-1], 6),
            }
        take_wall = self.take_wall_s
        phase_sum = sum(phase_total.values())
        out = {
            **self.meta,
            "rank": self.rank,
            "op": self.op,
            "enabled": self.enabled,
            "started_at": self.wall0,
            "take_wall_s": round(take_wall, 6),
            "phases": {k: round(v, 6) for k, v in phase_total.items()},
            "phase_coverage": (
                round(min(phase_sum / take_wall, 1.0), 4) if take_wall > 0 else 0.0
            ),
            "stages": stages,
            "counters": counters,
            "gauges": gauges,
            "events": len(events),
        }
        if io_hist:
            out["io_histograms"] = io_hist
        if probes:
            out["probe"] = probe_aggregate(probes)
            # Drift-immune roofline fraction: the operation's payload
            # throughput over its NON-PROBE wall-clock, against the
            # ceiling the interleaved probes measured through the same
            # engine moments apart — no separate roofline session whose
            # disk window the take never shared. Takes judge the write
            # leg; restores judge the read leg.
            adj_wall = max(take_wall - out["probe"].get("elapsed_s", 0.0), 1e-9)
            if self.meta.get("kind") == "restore":
                ceiling = out["probe"].get("read_gbps_p50")
                payload = counters.get("storage.bytes_read", 0)
                if ceiling and payload:
                    out["restore_roofline_fraction"] = round(
                        (payload / adj_wall / 1e9) / ceiling, 4
                    )
            else:
                ceiling = out["probe"].get("write_gbps_p50")
                payload = counters.get("storage.bytes_written", 0)
                if ceiling and payload:
                    out["roofline_fraction"] = round(
                        (payload / adj_wall / 1e9) / ceiling, 4
                    )
        return out

    def chrome_trace_events(self) -> List[Dict[str, Any]]:
        """Chrome trace-event list: complete ("X") events for spans,
        instant ("i") events for faults/retries, ts/dur in microseconds
        from ``t0`` (``t0_monotonic`` in the process_name event's args:
        ``t0_monotonic + ts`` is the absolute monotonic reading), pid =
        rank, tid = recording thread name. A span's ``args`` carry its
        ``id``, ``kind``, ``op`` and ``parent`` beside its attributes."""
        with self._lock:
            spans = list(self._spans)
            events = list(self._events)
        out: List[Dict[str, Any]] = [
            {
                "name": "process_name",
                "ph": "M",
                "pid": self.rank,
                "tid": 0,
                "args": {
                    "name": f"tpusnap rank {self.rank}",
                    "t0_monotonic": self.t0,
                },
            }
        ]
        for r in spans:
            out.append(
                {
                    "name": r.name,
                    "ph": "X",
                    "cat": "phase" if r.kind == PHASE else "op",
                    "ts": round((r.start - self.t0) * 1e6, 1),
                    "dur": round(r.duration_s * 1e6, 1),
                    "pid": self.rank,
                    "tid": r.thread,
                    "args": {
                        **r.attrs,
                        "id": r.id,
                        "kind": r.kind,
                        "op": r.op,
                        "parent": r.parent,
                    },
                }
            )
        for name, ts, thread, attrs in events:
            ev = {
                "name": name,
                "ph": "i",
                "cat": "event",
                "s": "p",
                "ts": round(ts * 1e6, 1),
                "pid": self.rank,
                "tid": thread,
            }
            if attrs:
                ev["args"] = attrs
            out.append(ev)
        return out

    def to_json(self) -> str:
        return json.dumps(
            {
                "rank": self.rank,
                "summary": self.summary(),
                "traceEvents": self.chrome_trace_events(),
            },
            sort_keys=False,
        )


# -------------------------------------------------------------- holder

WATCH_PERIOD_S = 0.005

# What a sampled frame's code object is, looked up once a code object:
# this package's; the user's (anything outside the standard library and
# the installed packages); an installed package's other than jax; the
# standard library's or jax's, with four functions of them told apart.
(_F_USER, _F_TPUSNAP, _F_THIRD, _F_LIB, _F_WAIT, _F_PUT, _F_SELECT, _F_RUN_ONCE) = range(8)
_PKG_DIR = os.path.dirname(os.path.abspath(__file__)) + os.sep
_STDLIB_DIR = os.path.dirname(os.path.abspath(os.__file__)) + os.sep
# The functions of jax in which a thread waits for the device to finish,
# and the one under which it hands the device an array.
_JAX_WAITS = frozenset({"block_until_ready", "device_get", "_value"})
_JAX_PUTS = frozenset({"device_put"})
_frame_kinds: Dict[Any, int] = {}


def _frame_kind(code: Any) -> int:
    kind = _frame_kinds.get(code)
    if kind is None:
        path, name = code.co_filename, code.co_name
        parts = path.split(os.sep)
        if path.startswith(_PKG_DIR):
            kind = _F_TPUSNAP
        elif "jax" in parts or "jaxlib" in parts:
            kind = _F_WAIT if name in _JAX_WAITS else _F_PUT if name in _JAX_PUTS else _F_LIB
        elif "site-packages" in parts or "dist-packages" in parts:
            kind = _F_THIRD
        elif path.startswith("<") or path.startswith(_STDLIB_DIR):
            kind = (
                _F_SELECT if name == "select" and parts[-1] == "selectors.py"
                else _F_RUN_ONCE if name == "_run_once" and parts[-1] == "base_events.py"
                else _F_LIB
            )
        else:
            kind = _F_USER
        _frame_kinds[code] = kind
    return kind


def classify_caller(frame: Any) -> Tuple[str, Any]:
    """``(class, (code, line))`` of the calling thread's stack, innermost
    frame first: ``tpusnap`` where the innermost frame outside jax, the
    standard library and the installed packages is this package's
    (``wait_staged``, ``wait``, ``done``, a second ``async_take``); else
    ``wait_device`` under a ``block_until_ready`` (``device_get``, an
    array's ``_value``) of jax, ``transfer`` under a ``device_put`` of
    jax; else ``other``: the user's code, and a jitted call's dispatch,
    which has no Python frame. The site is that innermost frame (an
    installed package's where the stack holds no other)."""
    under, fallback, depth = None, None, 0
    top = frame
    while frame is not None and depth < 64:
        kind = _frame_kind(frame.f_code)
        if kind == _F_TPUSNAP:
            return "tpusnap", (frame.f_code, frame.f_lineno)
        if kind == _F_USER:
            return under or "other", (frame.f_code, frame.f_lineno)
        if kind == _F_THIRD and fallback is None:
            fallback = (frame.f_code, frame.f_lineno)
        elif under is None and kind == _F_WAIT:
            under = "wait_device"
        elif under is None and kind == _F_PUT:
            under = "transfer"
        frame, depth = frame.f_back, depth + 1
    return under or "other", fallback or (top.f_code, top.f_lineno)


def classify_loop(frame: Any) -> Tuple[Optional[str], Any]:
    """``idle`` where the loop's thread stands in the selector,
    ``callback`` where it runs anything else under the loop (its site:
    the innermost frame outside the standard library and jax), None where the
    thread is not running a loop at all."""
    if _frame_kind(frame.f_code) == _F_SELECT:
        return "idle", None
    site, depth = None, 0
    while frame is not None and depth < 64:
        kind = _frame_kind(frame.f_code)
        if kind == _F_RUN_ONCE:
            return "callback", site
        if site is None and kind in (_F_USER, _F_TPUSNAP, _F_THIRD):
            site = (frame.f_code, frame.f_lineno)
        frame, depth = frame.f_back, depth + 1
    return None, None


def _site_name(site: Any) -> Optional[str]:
    if site is None:
        return None
    code, line = site
    return f"{os.path.basename(code.co_filename)}:{code.co_name}:{line}"


def thread_key() -> Tuple[int, int, Optional[int]]:
    """The calling thread as a watch follows it: its ``threading`` ident,
    its native id, and the id of its CPU-time clock (taken by the thread
    itself, while it certainly lives), None where the platform has none."""
    ident = threading.get_ident()
    try:
        clock: Optional[int] = time.pthread_getcpuclockid(ident)
    except Exception:
        clock = None
    return ident, threading.get_native_id(), clock


class _ThreadClocks:
    """One thread's nanoseconds on a CPU (its CPU-time clock) and
    nanoseconds runnable and waiting for one (the second field of
    ``/proc/self/task/<tid>/schedstat``, kept open). ``read`` gives None
    for what the platform does not give: a sandboxed kernel may have no
    schedstat (the chip machine's has none), and then there is no
    run-queue reading."""

    def __init__(self, native_id: int, cpu_clock: Optional[int]) -> None:
        self.cpu_clock = cpu_clock
        try:
            self.fd: Optional[int] = os.open(
                f"/proc/self/task/{native_id}/schedstat", os.O_RDONLY
            )
        except OSError:
            self.fd = None

    def read(self) -> Tuple[Optional[int], Optional[int]]:
        cpu = runq = None
        if self.cpu_clock is not None:
            try:
                cpu = time.clock_gettime_ns(self.cpu_clock)
            except OSError:  # the thread is gone
                self.cpu_clock = None
        if self.fd is not None:
            try:
                runq = int(os.pread(self.fd, 96, 0).split()[1])
            except (OSError, ValueError, IndexError):
                self.close()
        return cpu, runq

    def close(self) -> None:
        fd, self.fd = self.fd, None
        if fd is not None:
            try:
                os.close(fd)
            except OSError:
                pass


class _Track:
    """One watched thread's open span: its class, since when, how many
    ticks saw it, at which sites, and the thread's clocks at its start."""

    def __init__(
        self, name: str, ident: int, native_id: int, cpu_clock: Optional[int],
        start: float,
    ) -> None:
        self.name, self.ident, self.native_id = name, ident, native_id
        self.cls: Optional[str] = None
        self.seen = False
        self.start = start
        self.samples = 0
        self.sites: Dict[Any, int] = {}
        self.clocks = _ThreadClocks(native_id, cpu_clock)
        self.clocks_last = self.clocks_start = self.clocks.read()
        self.carry_ns = [0, 0]  # what a counter in microseconds left over
        self.annotation: Any = None


class HolderWatch:
    """What kept the thread that called ``async_take`` / ``restore``, and
    the thread that runs the operation's event loop, from running: both
    sampled every :data:`WATCH_PERIOD_S` from :meth:`begin` to :meth:`end`
    on the RSS sampler's thread (``RSSSampler``'s ``rider``), which this
    operation has anyway. Only an operation that began with a
    ``MetricsSink`` registered has one.

    A tick takes ``sys._current_frames()`` once and classes the two
    threads' stacks (:func:`classify_caller`, :func:`classify_loop`).
    Consecutive ticks of one class are one span ``caller.<class>`` /
    ``loop.<class>`` (kind ``wait``; thread ``caller`` / ``loop``),
    recorded when the class changes: attrs ``site`` (``file:function:line``
    of the frame most seen), ``samples``, ``thread`` and, where the kernel
    has them, ``cpu_ms`` / ``runq_ms`` of that thread over the span (its
    CPU-time clock; schedstat). While a caller class lasts this thread
    holds open the profiler annotation ``tpusnap-caller:<class>``.
    Counters a tick: ``caller.cpu_us`` / ``loop.cpu_us`` and
    ``caller.runq_us`` / ``loop.runq_us`` (the same clocks' growth; a
    counter the platform cannot feed is absent), ``watch.samples``,
    ``watch.late_us`` (how far the tick overshot its period: what any
    Python thread pays here to get the lock and a core back; gauge
    ``watch.late_max_us``), ``watch.tick_us`` (the tick's own wall time:
    some 50 us of Python, and more where the thread lost the lock or its
    core inside it). At the end: ``watch.cpu_us`` (this thread's
    own CPU time while it watched) and, of a take,
    ``take.process_cpu_us`` (user + system time of the whole process from
    the take's start to its end)."""

    def __init__(
        self, rec: "TakeTelemetry", caller: Tuple[int, int, Optional[int]]
    ) -> None:
        self.rec = rec
        self.caller = caller  # as thread_key() gives it
        self.loop: Optional[Tuple[int, int, Optional[int]]] = None  # the drain says
        self.period_s: Optional[float] = None  # None: not watching
        self._lock = threading.Lock()  # a tick against flush()
        self._tracks: Dict[str, _Track] = {}
        self._thread_cpu0: Optional[float] = None
        self._rusage0 = self._process_cpu_s()

    @staticmethod
    def _process_cpu_s() -> Optional[float]:
        try:
            import resource

            usage = resource.getrusage(resource.RUSAGE_SELF)
            return usage.ru_utime + usage.ru_stime
        except Exception:
            return None

    def begin(self) -> None:
        with self._lock:
            self._tracks["caller"] = _Track("caller", *self.caller, time.monotonic())
            self.period_s = WATCH_PERIOD_S

    # --- on the sampler's thread ----------------------------------------

    def tick(self, now: float, late_s: float) -> None:
        try:
            self._tick(now, late_s)
        except Exception:  # telemetry never fails a take: stop watching
            self.period_s = None
            logger.debug("holder watch stopped", exc_info=True)

    def _tick(self, now: float, late_s: float) -> None:
        frames = sys._current_frames()
        with self._lock:
            if self._thread_cpu0 is None:
                self._thread_cpu0 = time.thread_time()
            loop = self.loop
            track = self._tracks.get("loop")
            if loop is not None and (track is None or track.ident != loop[0]):
                if track is not None:
                    self._switch(track, now, None)
                    track.clocks.close()
                self._tracks["loop"] = _Track("loop", *loop, now)
            for track in self._tracks.values():
                frame = frames.get(track.ident)
                if frame is None:
                    cls, site = None, None
                elif track.name == "caller":
                    cls, site = classify_caller(frame)
                else:
                    cls, site = classify_loop(frame)
                if cls != track.cls:
                    self._switch(track, now, cls)
                if cls is not None:
                    track.samples += 1
                    if site is not None:
                        track.sites[site] = track.sites.get(site, 0) + 1
                self._count_clocks(track)
        del frames
        late_us = int(late_s * 1e6)
        rec = self.rec
        incr("watch.samples", rec=rec)
        if late_us:
            incr("watch.late_us", late_us, rec=rec)
            rec.gauge_max("watch.late_max_us", late_us)
        incr("watch.tick_us", int((time.monotonic() - now) * 1e6), rec=rec)

    def _count_clocks(self, track: _Track) -> None:
        last = track.clocks_last
        got = track.clocks_last = track.clocks.read()
        for i, what in enumerate(("cpu_us", "runq_us")):
            if got[i] is None or last[i] is None:
                continue
            ns = got[i] - last[i] + track.carry_ns[i]
            track.carry_ns[i] = ns % 1000
            if ns >= 1000:
                incr(f"{track.name}.{what}", ns // 1000, rec=self.rec)

    def _record(self, track: _Track, now: float) -> None:
        """The track's open span, as far as it has come; it goes on from
        now under the same class."""
        if track.cls is not None and now > track.start:
            attrs: Dict[str, Any] = {"samples": track.samples, "thread": track.native_id}
            if track.sites:
                attrs["site"] = _site_name(max(track.sites, key=track.sites.get))
            for i, what in enumerate(("cpu_ms", "runq_ms")):
                if None not in (track.clocks_last[i], track.clocks_start[i]):
                    attrs[what] = round(
                        (track.clocks_last[i] - track.clocks_start[i]) / 1e6, 3
                    )
            self.rec._keep(
                SpanRecord(
                    next(_span_ids), f"{track.name}.{track.cls}", track.start, now,
                    track.name, WAIT, self.rec.op, None, attrs,
                )
            )
        if track.cls is not None or track.seen:
            track.start = now  # a track's first span starts where the watch began
        track.seen = True
        track.samples, track.sites, track.clocks_start = 0, {}, track.clocks_last

    def _switch(self, track: _Track, now: float, cls: Optional[str]) -> None:
        self._record(track, now)
        track.cls = cls
        if track.name == "caller":
            if track.annotation is not None:
                track.annotation.__exit__(None, None, None)
            track.annotation = (
                _annotate(cls, self.rec.op, prefix="tpusnap-caller:")
                if cls is not None
                else None
            )

    def end(self) -> None:
        """The sampler's thread is exiting: close what is open."""
        with self._lock:
            watched = self.period_s is not None
            self.period_s = None
            now = time.monotonic()
            for track in self._tracks.values():
                self._switch(track, now, None)
                track.clocks.close()
            self._tracks.clear()
        if not watched:
            return
        rec = self.rec
        if self._thread_cpu0 is not None:
            incr(
                "watch.cpu_us",
                int((time.thread_time() - self._thread_cpu0) * 1e6),
                rec=rec,
            )
        cpu_s = self._process_cpu_s()
        if rec.meta.get("kind") == "take" and None not in (cpu_s, self._rusage0):
            incr("take.process_cpu_us", int((cpu_s - self._rusage0) * 1e6), rec=rec)

    # --- on any thread ----------------------------------------------------

    def flush(self) -> None:
        """Hand the open spans to the record as far as they have come
        (a take's trace is persisted before the take ends)."""
        with self._lock:
            now = time.monotonic()
            for track in self._tracks.values():
                self._record(track, now)


def holder_table(trace_events: List[Dict[str, Any]], summary: Dict[str, Any]) -> Dict[str, Any]:
    """What the watch and :func:`run_handoff` recorded of one rank's
    operation, from its persisted trace: seconds by ``caller.<class>``
    with the three sites most seen, seconds by ``loop.<class>``, the sum
    of each ``<name>.resumed``, and the ``caller.*`` / ``loop.*`` /
    ``watch.*`` / ``take.*`` counters. Empty where nothing was recorded."""
    tracks: Dict[str, Dict[str, Dict[str, Any]]] = {"caller": {}, "loop": {}}
    resumed: Dict[str, float] = {}
    for ev in trace_events:
        name = ev.get("name", "")
        if ev.get("ph") != "X":
            continue
        seconds = ev.get("dur", 0.0) / 1e6
        track, _, cls = name.partition(".")
        if track in tracks and ev.get("tid") == track:
            row = tracks[track].setdefault(cls, {"seconds": 0.0, "sites": {}})
            row["seconds"] += seconds
            site = (ev.get("args") or {}).get("site")
            if site:
                row["sites"][site] = row["sites"].get(site, 0.0) + seconds
        elif name.endswith(".resumed"):
            resumed[name] = resumed.get(name, 0.0) + seconds
    for rows in tracks.values():
        for row in rows.values():
            row["sites"] = sorted(row["sites"].items(), key=lambda kv: -kv[1])[:3]
    counters = {
        k: v
        for k, v in (summary.get("counters") or {}).items()
        if k.split(".")[0] in ("caller", "loop", "watch", "take")
    }
    gauges = {k: v for k, v in (summary.get("gauges") or {}).items() if k.startswith("watch.")}
    if not (tracks["caller"] or tracks["loop"] or resumed):
        return {}
    return {**tracks, "resumed": resumed, "counters": counters, "gauges": gauges}


# --------------------------------------------- ambient current recorder

# The take installs its recorder process-globally; background threads
# (async commit drain) overlay it thread-locally via use() so a NEWER
# take's global install cannot steal their spans.
_global_current: Optional[TakeTelemetry] = None
_tls = threading.local()


def current() -> Optional[TakeTelemetry]:
    rec = getattr(_tls, "current", None)
    return rec if rec is not None else _global_current


def _job_id() -> str:
    """The job identity every summary carries (``meta["job_id"]`` —
    concurrent jobs sharing a telemetry/metrics dir stay attributable).
    Best-effort: identity must never fail a take."""
    try:
        from .knobs import get_job_id

        return get_job_id()
    except Exception:
        return "job"


def _begin_common() -> None:
    # Fresh take/restore: re-arm the one-warning-per-sink budget and
    # reconcile env-driven export sinks (TPUSNAP_METRICS_EXPORT may
    # have changed since the last take; best-effort, never fatal).
    _reset_sink_warnings()
    try:
        from .metrics_export import install_env_sinks

        install_env_sinks()
    except Exception:
        logger.warning(
            "Failed to install metrics export sinks (non-fatal)",
            exc_info=True,
        )


def begin_take(rank: int) -> TakeTelemetry:
    """Create a take recorder and install it as the process-global
    current. Pipeline layers then record through the module-level
    span()/incr()/event() without threading a handle."""
    global _global_current
    _begin_common()
    # Fresh black box per take: the flight sidecar is a per-take
    # artifact, and a crashed take's verdict must not count previous
    # takes' stalls/evictions (restores do NOT reset — they overlay).
    try:
        _flight.recorder().mark_take_start()
    except Exception:
        logger.debug("flight ring reset failed", exc_info=True)
    rec = TakeTelemetry(rank)
    rec.meta["kind"] = "take"
    rec.meta["job_id"] = _job_id()
    _global_current = rec
    return rec


def begin_restore(
    rank: int, caller: Optional[Tuple[int, int, Optional[int]]] = None
) -> TakeTelemetry:
    """Create a restore recorder (NOT installed globally — restores
    overlay it thread-locally via :func:`use` so an in-flight take's
    global recorder is never disturbed). ``caller``: see
    :class:`TakeTelemetry`."""
    _begin_common()
    rec = TakeTelemetry(rank, kind="restore", caller=caller)
    rec.meta["kind"] = "restore"
    rec.meta["job_id"] = _job_id()
    return rec


def release_global(rec: TakeTelemetry) -> None:
    """Uninstall ``rec`` as the process-global current (no-op when a
    newer take already replaced it). async_take calls this when control
    returns to training — the background drain keeps recording through
    captured references and a thread-local :func:`use` overlay."""
    global _global_current
    if _global_current is rec:
        _global_current = None


def end_take(rec: TakeTelemetry) -> None:
    """Finalize + uninstall (only if still installed) and publish the
    summary: LAST_TAKE_SUMMARY, the sinks' on_take_summary, and — for
    COMPLETED takes only — one cross-run history event."""
    global LAST_TAKE_SUMMARY
    # The auto-tuner's overlay is scoped to the take that applied it
    # (end_take is the chokepoint every take path — sync, async,
    # aborted — funnels through); knob reads afterwards see the plain
    # environment again. The summary below still carries meta["tuned"].
    try:
        from .knobs import clear_tuned_plan

        clear_tuned_plan()
    except Exception:
        pass
    rec.close()
    release_global(rec)
    summary = rec.summary()
    LAST_TAKE_SUMMARY = summary
    _notify("on_take_summary", summary)
    try:
        from .history import record_summary

        record_summary("take", summary)
    except Exception:
        logger.debug("history record failed", exc_info=True)


def publish_restore_summary(summary: Dict[str, Any]) -> None:
    """Restore-side counterpart of :func:`end_take`'s publication step:
    LAST_RESTORE_SUMMARY, the sinks' on_restore_summary, and — for
    completed restores — one history event."""
    global LAST_RESTORE_SUMMARY
    LAST_RESTORE_SUMMARY = summary
    _notify("on_restore_summary", summary)
    try:
        from .history import record_summary

        record_summary("restore", summary)
    except Exception:
        logger.debug("history record failed", exc_info=True)


@contextmanager
def use(rec: Optional[TakeTelemetry]) -> Generator[None, None, None]:
    """Thread-local overlay: make ``rec`` the current recorder on THIS
    thread (async commit / background restore threads)."""
    prev = getattr(_tls, "current", None)
    _tls.current = rec
    try:
        yield
    finally:
        _tls.current = prev


@contextmanager
def span(
    name: str,
    phase: bool = False,
    *,
    kind: Optional[str] = None,
    rec: Optional[TakeTelemetry] = None,
    **attrs: Any,
) -> Generator[OpenSpan, None, None]:
    """Record a span into ``rec`` or the ambient recorder (see
    :meth:`TakeTelemetry.span`); no-op (one lookup) when no take is in
    flight or span capture is knob-disabled."""
    rec = rec if rec is not None else current()
    if rec is None or not rec.enabled:
        yield OpenSpan(0, attrs)
        return
    with rec.span(name, phase=phase, kind=kind, **attrs) as sp:
        yield sp


def _request_recorder() -> Optional[TakeTelemetry]:
    """The recorder a hand-off belongs to: the one whose span is open
    where it is submitted (the request's), else the ambient one."""
    ambient = _ambient.get()
    if ambient is not None and ambient[0]._finalized_wall_s is None:
        return ambient[0]
    return current()


def handoff(
    name: str, fn: Callable[..., Any], work: Union[bool, str] = True, **attrs: Any
) -> Callable[..., Any]:
    """``fn`` wrapped for an executor, so that its wait for a worker and
    its body are told apart (see :meth:`TakeTelemetry.handoff`). The
    recorder is the one whose span is open where this is called (the
    request's), else the ambient one; with neither, ``fn`` as it is."""
    rec = _request_recorder()
    if rec is None:
        return fn
    return rec.handoff(name, fn, work=work, **attrs)


def note_loop_thread() -> None:
    """The calling thread is about to run the ambient operation's event
    loop (see :meth:`TakeTelemetry.note_loop_thread`)."""
    rec = current()
    if rec is not None:
        rec.note_loop_thread()


async def run_handoff(
    executor: Any,
    name: str,
    fn: Callable[..., Any],
    *args: Any,
    work: Union[bool, str] = True,
    submit: Optional[Callable[[Any, Callable[[], Any]], Any]] = None,
    **attrs: Any,
) -> Any:
    """Run ``fn(*args)`` on ``executor`` through :func:`handoff` and await
    it: what every executor call of the pipeline goes through. Beside the
    hand-off's spans on the worker it records, on the event loop's thread,
    ``<name>.resumed`` (kind ``wait``, a child of the request's span, no
    annotation: nothing runs): from the instant the worker's body ended
    (the work span's own end, stamped on the worker) to the instant this
    coroutine runs again. That is how late the loop was for a request
    that had finished; with it the request's await span closes:
    ``queued + work + resumed`` a trip, plus what the coroutine itself ran
    between its trips. ``submit(executor, fn)`` stands in for
    ``loop.run_in_executor`` where the plug-in tracks its futures (then
    ``fn`` takes no arguments)."""
    loop = asyncio.get_running_loop()
    rec = _request_recorder()
    if rec is None or not rec.enabled:
        if submit is not None:
            return await submit(executor, fn)
        return await loop.run_in_executor(executor, fn, *args)
    ended: List[float] = []
    parent = rec.ambient_parent()
    wrapped = rec.handoff(name, fn, work=work, ended=ended, **attrs)
    try:
        if submit is not None:
            return await submit(executor, wrapped)
        return await loop.run_in_executor(executor, wrapped, *args)
    finally:
        if ended:
            rec._keep(
                SpanRecord(
                    next(_span_ids), f"{name}.resumed", ended[0], time.monotonic(),
                    threading.current_thread().name, WAIT, rec.op, parent, {},
                )
            )


def event(name: str, **attrs: Any) -> None:
    rec = current()
    if rec is not None:
        rec.event(name, **attrs)


def incr(name: str, n: int = 1, rec: Optional[TakeTelemetry] = None) -> None:
    """Always-on counter: bumps the process-global counter AND the
    in-flight take's (the ambient one, or an explicit ``rec`` captured
    by code that outlives the take's global install). Sinks are
    notified with the process-global cumulative value — one monotonic
    domain regardless of take boundaries."""
    with _counters_lock:
        global_value = _global_counters.get(name, 0) + n
        _global_counters[name] = global_value
    rec = rec if rec is not None else current()
    if rec is not None:
        rec.incr(name, n)
    _notify("on_counter", name, n, global_value)


def gauge_max(name: str, value: float) -> None:
    rec = current()
    if rec is not None:
        rec.gauge_max(name, value)


class PhaseMarker:
    """Sequential PHASE-span recorder for a linear pipeline: each call
    records a phase span from the previous mark (or construction) to
    now, so the recorded phases tile the timeline with no gaps — which
    is what makes the trace CLI's wall-clock coverage meaningful.

    A mark names the phase that just ended. Where the caller also names
    the one that begins (``then=``, or :meth:`begin`), the marker holds
    ``tpusnap:<name>`` open in the profiler's trace from mark to mark,
    and spans opened meanwhile on this thread take the phase as their
    parent. The phases of one marker run on one thread."""

    def __init__(
        self, rec: Optional[TakeTelemetry] = None, from_start: bool = False
    ) -> None:
        self.rec = rec if rec is not None else current()
        # from_start anchors the first phase at the recorder's t0, so
        # recorder-construction overhead (RSS sampler thread spawn)
        # cannot open a coverage hole before the first phase.
        self.last = (
            self.rec.now()
            if self.rec is not None and self.rec.enabled and not from_start
            else 0.0
        )
        self._begun: Optional[Tuple[str, int]] = None  # (name, span id)

    def begin(self, name: str) -> None:
        """Say which phase runs from here to the next mark."""
        rec = self.rec
        if rec is None or not rec.enabled or rec._finalized_wall_s is not None:
            return
        self._end_begun()
        self._begun = (name, next(_span_ids))
        _ambient.set((rec, self._begun[1]))
        rec._phase_annotation = _annotate(name, rec.op)

    def _end_begun(self) -> None:
        if self._begun is None:
            return
        self._begun = None
        _ambient.set(None)
        annotation, self.rec._phase_annotation = self.rec._phase_annotation, None
        if annotation is not None:
            annotation.__exit__(None, None, None)

    def __call__(self, name: str, then: Optional[str] = None, **attrs: Any) -> None:
        if self.rec is None or not self.rec.enabled:
            return
        span_id = (
            self._begun[1] if self._begun is not None and self._begun[0] == name else None
        )
        self._end_begun()
        now = self.rec.now()
        self.rec.record_span(
            name, self.last, now - self.last, phase=True, span_id=span_id, **attrs
        )
        self.rec.note_phase(name)
        self.last = now
        if then is not None:
            self.begin(then)


def phase_marker(
    from_start: bool = False, first: Optional[str] = None
) -> PhaseMarker:
    """A marker on the ambient recorder; ``first`` names the phase that
    begins here (see :meth:`PhaseMarker.begin`)."""
    marker = PhaseMarker(from_start=from_start)
    if first is not None:
        marker.begin(first)
    return marker


# -------------------------------------------------------------- rollup


def rollup_summaries(summaries: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Cross-rank rollup rank 0 folds into the metadata extras: per
    stage, the p50/max over ranks of each rank's TOTAL time in that
    stage — WITH the straggler's rank id (``max_rank``); summed
    counters; max gauges; slowest-rank wall-clock; and ``phase_skew``,
    the per-phase straggler attribution (slowest rank + max/p50 skew)
    the stall watchdog's post-mortem reads."""
    summaries = [s for s in summaries if s]
    if not summaries:
        return {}
    # (total_s, rank) pairs so the straggler keeps its rank id.
    stage_totals: Dict[str, List[Tuple[float, int]]] = {}
    phase_totals: Dict[str, List[Tuple[float, int]]] = {}
    counters: Dict[str, int] = {}
    gauges: Dict[str, float] = {}
    for i, s in enumerate(summaries):
        rank = s.get("rank", i)
        for name, agg in (s.get("stages") or {}).items():
            stage_totals.setdefault(name, []).append(
                (agg.get("total_s", 0.0), rank)
            )
        for name, v in (s.get("phases") or {}).items():
            phase_totals.setdefault(name, []).append((v, rank))
        for name, v in (s.get("counters") or {}).items():
            counters[name] = counters.get(name, 0) + v
        for name, v in (s.get("gauges") or {}).items():
            if v > gauges.get(name, float("-inf")):
                gauges[name] = v
    stages = {}
    for name, totals in sorted(stage_totals.items()):
        ts = sorted(totals)
        stages[name] = {
            "ranks": len(ts),
            "p50_s": round(ts[len(ts) // 2][0], 6),
            "max_s": round(ts[-1][0], 6),
            "max_rank": ts[-1][1],
        }
    phase_skew = {}
    for name, totals in sorted(phase_totals.items()):
        ts = sorted(totals)
        p50, mx = ts[len(ts) // 2][0], ts[-1][0]
        phase_skew[name] = {
            "p50_s": round(p50, 6),
            "max_s": round(mx, 6),
            "max_rank": ts[-1][1],
            "skew": round(mx / p50, 3) if p50 > 0 else None,
        }
    out = {
        "phase_skew": phase_skew,
        "ranks": len(summaries),
        "take_wall_s": round(max(s.get("take_wall_s", 0.0) for s in summaries), 6),
        "phase_coverage_min": round(
            min(s.get("phase_coverage", 0.0) for s in summaries), 4
        ),
        "stages": stages,
        "counters": counters,
        "gauges": gauges,
        "bytes_written": counters.get("storage.bytes_written", 0),
        "retry_attempts": counters.get("retry.attempts", 0),
        "budget_high_water_bytes": gauges.get("scheduler.budget_used_bytes"),
        "peak_rss_delta_bytes": gauges.get("peak_rss_delta_bytes"),
    }
    # Cross-rank I/O histogram merge: bucket-count sums per
    # "<op>.<PluginClass>" key, quantiles recomputed from the merge —
    # a rank's p99 outlier survives the fold instead of averaging away.
    io_merged = merge_io_histograms(
        [s.get("io_histograms") or {} for s in summaries]
    )
    if io_merged:
        out["io_histograms"] = io_merged
    # Roofline probes: the p50 fraction across ranks (the fleet
    # headline) plus the worst rank's, with its id (a single rank's slow
    # disk is a straggler story, not a fleet story). Takes fold
    # ``roofline_fraction`` (write lane), restores fold
    # ``restore_roofline_fraction`` (read lane) — same shape.
    any_fracs = False
    for field in ("roofline_fraction", "restore_roofline_fraction"):
        fracs = sorted(
            (s[field], s.get("rank", i))
            for i, s in enumerate(summaries)
            if isinstance(s.get(field), (int, float))
        )
        if not fracs:
            continue
        any_fracs = True
        out[field] = round(fracs[len(fracs) // 2][0], 4)
        out[f"{field}_min"] = round(fracs[0][0], 4)
        out[f"{field}_min_rank"] = fracs[0][1]
    if any_fracs:
        probe_ranks = [s["probe"] for s in summaries if s.get("probe")]
        if probe_ranks:
            out["probe"] = {
                "probes": sum(p.get("probes", 0) for p in probe_ranks)
            }
            for lane in ("write_gbps_p50", "read_gbps_p50"):
                ceilings = sorted(
                    p[lane] for p in probe_ranks if p.get(lane)
                )
                out["probe"][lane] = (
                    round(ceilings[len(ceilings) // 2], 4) if ceilings else None
                )
    return out
