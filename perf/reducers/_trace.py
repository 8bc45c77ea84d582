"""From the profiler's ``.xplane.pb`` to busy seconds, the longest device
operations and the longest idle gaps, each gap labelled by the tpusnap span
(from the sink, on the same process clock) that covered most of it.

Not a reducer of one metric: the harness calls ``summarise`` once in a
traced run, and the ``trace_idle_share`` reducer reads what it returns."""

import glob
import os

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
GAP_FLOOR_S = 1e-3


def merge(intervals):
    out = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return out


def self_seconds(events):
    """Seconds by name of ``(start, end, name)`` events of one line, each
    event's time less that of the events nested in it (a loop's body is
    counted under its own operations, not again under the loop)."""
    out, stack = {}, []

    def close():
        start, end, name, inner = stack.pop()
        out[name] = out.get(name, 0.0) + max(end - start - inner, 0.0)
        if stack:
            stack[-1][3] += end - start

    for start, end, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][1] <= start:
            close()
        stack.append([start, end, name, 0.0])
    while stack:
        close()
    return out


def short_name(name: str) -> str:
    """The trace names an operation by its whole HLO line: keep its name."""
    return name.split(" = ")[0].lstrip("%")[:80]


def label_gaps(busy, window, spans, top=10):
    """Idle gaps of one device inside ``window``, longest labels first.
    ``busy`` is merged intervals; ``spans`` are dicts with name/start/end on
    the same clock. A gap belongs to the span that covers most of it, and to
    ``host-other`` where no span covers any of it."""
    gaps, cursor = [], window[0]
    for start, end in busy:
        if start - cursor >= GAP_FLOOR_S:
            gaps.append((cursor, start))
        cursor = max(cursor, end)
    if window[1] - cursor >= GAP_FLOOR_S:
        gaps.append((cursor, window[1]))
    by_label = {}
    for g0, g1 in gaps:
        best, best_cover = "host-other", 0.0
        for s in spans:
            cover = min(g1, s["end"]) - max(g0, s["start"])
            if cover > best_cover:
                best, best_cover = s["name"], cover
        by_label[best] = by_label.get(best, 0.0) + (g1 - g0)
    return sorted(([k, v] for k, v in by_label.items()), key=lambda kv: -kv[1])[:top]


def summarise(tracer, spans):
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(tracer.dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files or tracer.t_start is None or tracer.t_stop is None:
        return None
    data = ProfileData.from_file(files[-1])
    anchor_ns, planes, per_device, op_seconds = None, [], [], {}
    for plane in data.planes:
        lines = list(plane.lines)
        planes.append({"plane": plane.name, "lines": [ln.name for ln in lines][:12]})
        if plane.name.startswith("/host:") and anchor_ns is None:
            for ln in lines:
                for ev in ln.events:
                    if ev.name == "perf_anchor":
                        anchor_ns = ev.start_ns
                        break
        if not plane.name.startswith(DEVICE_PLANE):
            continue
        for ln in lines:
            if ln.name != OPS_LINE:
                continue
            events = [
                (ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9, short_name(ev.name))
                for ev in ln.events
            ]
            if events:
                per_device.append(merge((s, e) for s, e, _ in events))
                for name, seconds in self_seconds(events).items():
                    op_seconds[name] = op_seconds.get(name, 0.0) + seconds
    if not per_device:
        return None
    window_s = tracer.t_stop - tracer.t_start
    busy_s = sum(sum(e - s for s, e in busy) for busy in per_device) / len(per_device)
    # The sink's spans on the trace's clock: the anchor was written at a
    # known instant of this process's monotonic clock.
    if anchor_ns is not None:
        shift = anchor_ns * 1e-9 - tracer.anchor_monotonic
        shifted = [dict(s, start=s["start"] + shift, end=s["end"] + shift) for s in spans]
        window = (tracer.t_start + shift, tracer.t_stop + shift)
    else:
        shifted, window = [], (per_device[0][0][0], per_device[0][-1][1])
    device_ops = sorted(([k, v / len(per_device)] for k, v in op_seconds.items()),
                        key=lambda kv: -kv[1])[:10]
    return {
        "planes": planes,
        "busy_s": busy_s,
        "window_s": window_s,
        "anchored": anchor_ns is not None,
        "breakdown": {
            "device_ops": device_ops,
            "idle_gaps": label_gaps(per_device[0], window, shifted),
        },
    }
