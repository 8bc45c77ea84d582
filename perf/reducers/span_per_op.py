"""Busy time of the named spans, summed per operation (a save or a
restore), then the median over the window's operations, in milliseconds.
Busy time is summed over threads; it is not wall time."""

import statistics


def per_op(obs, names, field="duration"):
    """One total per operation (0 where none of the spans fired in it)."""
    out = []
    for op in obs["ops"]:
        out.append(sum(
            s["bytes"] if field == "bytes" else s["end"] - s["start"]
            for s in obs["spans"]
            if s["name"] in names and op["t_call"] <= s["end"] <= op["t_done"]
        ))
    return out


def reduce(obs, spans):
    if not any(s["name"] in spans for s in obs["spans"]):
        return None  # the span does not fire on this path: nothing to read
    return statistics.median(per_op(obs, set(spans))) * 1e3
