"""Of device 0's idle seconds in the traced slice, the share that no
``tpusnap:`` annotation covers, in per cent; read from the run's
``.xplane.pb`` alone, where the program's annotations and the device's
operations share the profiler's clock (no anchor, no sink).

Idle gaps are the complement of the merged ``XLA Ops`` events of device 0
between its first and its last operation, those over 1 ms (``_trace.py``'s
floor). Each gap is cut at the boundaries of the annotations that overlap
it, and each piece goes to the innermost annotation that covers it: one of
the step-dispatching thread's (the line that holds the harness's
``perf_anchor``) if there is one, else one of any thread's, else to none.
The whole table is printed on a ``perf idle_by_leaf:`` line.

The harness keeps the trace under ``<work_dir>/trace`` and exports
``<work_dir>/telemetry`` as ``TPUSNAP_TELEMETRY_DIR``; that is how the
trace is found. A program that writes no annotation gives nothing to read.
"""

import glob
import os
import re

from perf.reducers._trace import DEVICE_PLANE, GAP_FLOOR_S, OPS_LINE, merge

PREFIX = "tpusnap:"
ANCHOR = "perf_anchor"
NONE = "(none)"


def idle_gaps(busy):
    """The gaps over the floor between merged busy intervals."""
    return [
        (end, start)
        for (_, end), (start, _) in zip(busy, busy[1:])
        if start - end >= GAP_FLOOR_S
    ]


def attribute(gaps, annotations, main_thread=None):
    """Idle seconds by annotation name. ``annotations`` are ``(thread,
    start, end, name)``; ``gaps`` are ``(start, end)``."""
    by_leaf = {}
    for g0, g1 in gaps:
        over = [a for a in annotations if a[1] < g1 and a[2] > g0]
        cuts = sorted({g0, g1, *(t for a in over for t in a[1:3] if g0 < t < g1)})
        for p0, p1 in zip(cuts, cuts[1:]):
            covering = [a for a in over if a[1] <= p0 and a[2] >= p1]
            mine = [a for a in covering if a[0] == main_thread]
            # Innermost: annotations of one thread nest, so the shortest.
            leaf = min(mine or covering, key=lambda a: (a[2] - a[1], a[3]), default=None)
            name = leaf[3] if leaf else NONE
            by_leaf[name] = by_leaf.get(name, 0.0) + (p1 - p0)
    return by_leaf


def read_planes(path):
    """Device 0's merged busy intervals, the ``tpusnap:`` annotations of
    every host thread, and the thread that holds the anchor."""
    from jax.profiler import ProfileData

    busy, annotations, main_thread = [], [], None
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
    thread = 0
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            thread += 1
            for ev in ln.events:
                if ev.name == ANCHOR:
                    main_thread = thread
                elif ev.name.startswith(PREFIX):
                    start = ev.start_ns * 1e-9
                    annotations.append(
                        (thread, start, start + ev.duration_ns * 1e-9, ev.name[len(PREFIX):])
                    )
    return busy, annotations, main_thread


def reduce(obs):
    from perf import harness

    telemetry_dir = os.environ.get("TPUSNAP_TELEMETRY_DIR", "")
    trace_dir = os.path.join(os.path.dirname(telemetry_dir), "trace")
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not obs.get("trace") or not files:
        return None
    busy, annotations, main_thread = read_planes(files[-1])
    gaps = idle_gaps(busy)
    if not annotations or not gaps:
        return None
    by_leaf = attribute(gaps, annotations, main_thread)
    idle_s = sum(by_leaf.values())
    harness.say(
        "idle_by_leaf",
        idle_s=idle_s,
        anchored=main_thread is not None,
        by_leaf=sorted(([k, v] for k, v in by_leaf.items()), key=lambda kv: -kv[1]),
    )
    return 100.0 * by_leaf.get(NONE, 0.0) / idle_s
