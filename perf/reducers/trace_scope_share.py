"""Of device 0's busy seconds in the traced slice, the share spent in
operations that the program put under a named scope (``jax.named_scope``),
in per cent; read from the run's ``.xplane.pb`` alone, found as
``trace_unexplained_idle`` finds it.

The profiler names a device operation by its HLO line and keeps the
framework's name for it (the HLO ``op_name``: ``jit(train_step)/
jvp(moe.experts)/cond/...``) as the ``tf_op`` stat of the event's
*metadata*, which ``jax.profiler.ProfileData`` does not show. So the
file's own protobuf is read for that one table (``framework_names``: a
few fields of ``XSpace``, decoded from the wire format, no further
dependency), and the events come from ``ProfileData`` as everywhere else.

An operation belongs to the scope if ``scope`` (a prefix such as
``moe.``) starts a component of its framework name, or if its own name
starts with one of ``kernels`` (the compiler names its grouped-product
kernel ``ragged-dot-*`` and gives it no scope). Seconds are self time
(``_trace.self_seconds``): a loop's or a conditional's body counts under
its own operations. A fusion counts under the scope of the operation
that names it, so the split between neighbouring scopes is as exact as
the compiler's fusions are narrow. A program with no such scope, or a
trace with no device plane, gives nothing to read."""

import functools
import glob
import os
import re

from perf.reducers._trace import DEVICE_PLANE, OPS_LINE, self_seconds, short_name

FRAMEWORK_NAME_STAT = "tf_op"


def _varint(buf, i):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def fields(buf):
    """``(field number, value)`` of one protobuf message: an int for a
    varint, a ``memoryview`` for bytes, a string or a nested message."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire}")
        yield key >> 3, value


def _map_entry(buf):
    """A protobuf map's entry: ``(key, value message)``."""
    entry = dict(fields(buf))
    return entry.get(1, 0), entry.get(2, b"")


def framework_names(path):
    """``{HLO line: framework name}`` of device 0's operations, from the
    ``XSpace`` in ``path``: ``XSpace.planes`` (1) -> ``XPlane.name`` (2),
    ``.event_metadata`` (4), ``.stat_metadata`` (5); ``XEventMetadata.name``
    (2), ``.stats`` (5); ``XStat.metadata_id`` (1), ``.str_value`` (5),
    ``.ref_value`` (7); ``XStatMetadata.name`` (2)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    planes = []
    for number, plane in fields(space):
        if number == 1:
            name = next((bytes(v).decode() for n, v in fields(plane) if n == 2), "")
            if name.startswith(DEVICE_PLANE):
                planes.append((int(re.match(r"\d*", name[len(DEVICE_PLANE):]).group() or 0), plane))
    if not planes:
        return {}
    plane = min(planes, key=lambda p: p[0])[1]
    stat_names, events = {}, []
    for number, value in fields(plane):
        if number == 5:
            key, meta = _map_entry(value)
            stat_names[key] = next((bytes(v).decode() for n, v in fields(meta) if n == 2), "")
        elif number == 4:
            events.append(_map_entry(value)[1])
    out = {}
    for meta in events:
        name, framework = "", None
        for number, value in fields(meta):
            if number == 2:
                name = bytes(value).decode()
            elif number == 5:
                stat = dict(fields(value))
                if stat_names.get(stat.get(1)) == FRAMEWORK_NAME_STAT:
                    framework = (bytes(stat[5]).decode() if 5 in stat
                                 else stat_names.get(stat.get(7), ""))
        if framework is not None:
            out[name] = framework
    return out


def matcher(scope, kernels=(), names=None):
    """``event -> where it matched`` (``"kernel"``, ``"scope"``) or None.
    ``names`` maps an event's name to its framework name; an event that is
    not in it is looked at by its own name."""
    pattern = re.compile(r"(?<![A-Za-z0-9_.])" + re.escape(scope))
    names = names or {}

    def where(event):
        if short_name(event.name).startswith(tuple(kernels)):
            return "kernel"
        if pattern.search(names.get(event.name, event.name)):
            return "scope"
        return None

    return where


def scope_seconds(events, where):
    """``(seconds under the scope, busy seconds, events matched by where)``
    of one line's events, as self time by event."""
    by_event = self_seconds(
        [(ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9, i)
         for i, ev in enumerate(events)]
    )
    inside, matched = 0.0, {}
    for i, seconds in by_event.items():
        hit = where(events[i])
        if hit:
            inside += seconds
            matched[hit] = matched.get(hit, 0) + 1
    return inside, sum(by_event.values()), matched


@functools.lru_cache(maxsize=1)
def _device_operations(path):
    """Device 0's operations and their framework names; kept for the next
    scope read off the same file (with the profile they belong to)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = sorted(
        (p for p in data.planes if p.name.startswith(DEVICE_PLANE)),
        key=lambda p: int(re.match(r"\d*", p.name[len(DEVICE_PLANE):]).group() or 0),
    )
    events = [ev for ln in (planes[0].lines if planes else ()) if ln.name == OPS_LINE
              for ev in ln.events]
    return data, events, framework_names(path) if events else {}


def share_of_file(path, scope, kernels=()):
    """``(seconds under the scope, busy seconds, matched)`` of device 0."""
    _, events, names = _device_operations(path)
    if not events:
        return 0.0, 0.0, {}
    return scope_seconds(events, matcher(scope, kernels, names))


def reduce(obs, scope, kernels=()):
    from perf import harness

    telemetry_dir = os.environ.get("TPUSNAP_TELEMETRY_DIR", "")
    trace_dir = os.path.join(os.path.dirname(telemetry_dir), "trace")
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not obs.get("trace") or not files:
        return None
    inside, busy, matched = share_of_file(files[-1], scope, kernels)
    harness.say("scope_share", scope=scope, inside_s=inside, busy_s=busy, matched=matched)
    if not inside or not busy:
        return None
    return 100.0 * inside / busy
