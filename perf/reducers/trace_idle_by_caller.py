"""Device 0's idle seconds in the traced slice, after ``async_take`` handed
back, by where the thread that called it stood: read from the run's
``.xplane.pb`` alone, where the device's operations and the program's
``tpusnap-caller:<class>`` annotations share the profiler's clock (no
anchor, no sink).

The program's watch (``tpusnap.telemetry.HolderWatch``) samples the caller's
thread every 5 ms from ``async_take``'s return to the take's end and holds
one annotation open, on its own thread's line, while the sampled class
lasts: ``wait_device`` (under ``jax.block_until_ready``: the step was
dispatched), ``transfer`` (under ``jax.device_put``), ``tpusnap`` (inside
the package: ``wait_staged``, ``wait``) or ``other`` (the user's code, and a
jitted call's dispatch). The idle gaps are those of
``trace_unexplained_idle`` (the complement of device 0's merged ``XLA Ops``,
over 1 ms), clipped to the annotations' extent; each is cut at the
annotations' boundaries. ``waiting`` is the idle time under ``wait_device``;
``elsewhere`` is all the rest inside the extent: the other classes and what
no annotation covers. The whole table is printed once on a ``perf
idle_by_caller:`` line, with the slice's idle seconds outside the extent
(before the hand-back, after the take's end).

A program that writes no such annotation (one from before the watch, a run
with no sink, a rehearsal without a device plane) gives nothing to read.
"""

import glob
import os
import re

from perf.reducers._trace import DEVICE_PLANE, OPS_LINE, merge
from perf.reducers.trace_unexplained_idle import idle_gaps

PREFIX = "tpusnap-caller:"
WAITING = "wait_device"
NONE = "(none)"
OUTSIDE = "(outside)"

_tables = {}  # one reading of a trace serves both metrics


def attribute(gaps, annotations):
    """Idle seconds by the caller's class. ``gaps`` are ``(start, end)``;
    ``annotations`` are ``(start, end, class)`` of one thread's line, so no
    two overlap. A gap's part outside the annotations' extent goes to
    ``OUTSIDE``; inside it, what no annotation covers goes to ``NONE``."""
    by_class = {}

    def give(name, seconds):
        if seconds > 0:
            by_class[name] = by_class.get(name, 0.0) + seconds

    if not annotations:
        return by_class
    first = min(a[0] for a in annotations)
    last = max(a[1] for a in annotations)
    for g0, g1 in gaps:
        inside = (max(g0, first), min(g1, last))
        give(OUTSIDE, (g1 - g0) - max(inside[1] - inside[0], 0.0))
        if inside[1] <= inside[0]:
            continue
        covered = 0.0
        for a0, a1, name in annotations:
            cover = min(inside[1], a1) - max(inside[0], a0)
            if cover > 0:
                give(name, cover)
                covered += cover
        give(NONE, (inside[1] - inside[0]) - covered)
    return by_class


def split(by_class):
    """``(waiting, elsewhere)`` seconds of a table of ``attribute``."""
    waiting = by_class.get(WAITING, 0.0)
    inside = sum(v for k, v in by_class.items() if k != OUTSIDE)
    return waiting, inside - waiting


def read_planes(path):
    """Device 0's merged busy intervals and the ``tpusnap-caller:``
    annotations of every host line."""
    from jax.profiler import ProfileData

    busy, annotations = [], []
    planes = list(ProfileData.from_file(path).planes)
    devices = sorted(
        (p for p in planes if p.name.startswith(DEVICE_PLANE)),
        key=lambda p: int(re.match(r"\d*", p.name[len(DEVICE_PLANE):]).group() or 0),
    )
    for ln in devices[0].lines if devices else ():
        if ln.name == OPS_LINE:
            busy = merge(
                (ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
                for ev in ln.events
            )
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            for ev in ln.events:
                if ev.name.startswith(PREFIX):
                    start = ev.start_ns * 1e-9
                    annotations.append(
                        (start, start + ev.duration_ns * 1e-9, ev.name[len(PREFIX):])
                    )
    return busy, annotations


def _table(obs):
    from perf import harness

    telemetry_dir = os.environ.get("TPUSNAP_TELEMETRY_DIR", "")
    trace_dir = os.path.join(os.path.dirname(telemetry_dir), "trace")
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not obs.get("trace") or not files:
        return None
    if files[-1] not in _tables:
        busy, annotations = read_planes(files[-1])
        gaps = idle_gaps(busy)
        by_class = attribute(gaps, annotations) if gaps else {}
        _tables[files[-1]] = by_class or None
        if by_class:
            waiting, elsewhere = split(by_class)
            harness.say(
                "idle_by_caller",
                idle_s=sum(by_class.values()),
                waiting_s=waiting,
                elsewhere_s=elsewhere,
                annotated_s=sum(a1 - a0 for a0, a1, _ in annotations),
                by_class=sorted(([k, v] for k, v in by_class.items()), key=lambda kv: -kv[1]),
            )
    return _tables[files[-1]]


def reduce(obs, part):
    by_class = _table(obs)
    if not by_class:
        return None
    waiting, elsewhere = split(by_class)
    return {"waiting": waiting, "elsewhere": elsewhere}[part] * 1e3
