"""Wall time in which any of the named spans was open, per operation:
the union of their intervals (a second of two overlapping spans counts
once), then the median over the window's operations, in milliseconds.
Reads the spans' starts as well as their ends, so it suits spans that
the program records with their real start (not await spans rebuilt from
a duration)."""

import statistics

from perf.reducers._trace import merge


def per_op(obs, names):
    out = []
    for op in obs["ops"]:
        intervals = merge(
            (s["start"], s["end"])
            for s in obs["spans"]
            if s["name"] in names and op["t_call"] <= s["end"] <= op["t_done"]
        )
        out.append(sum(end - start for start, end in intervals))
    return out


def reduce(obs, spans):
    if not obs["ops"] or not any(s["name"] in spans for s in obs["spans"]):
        return None  # the span does not fire on this path: nothing to read
    return statistics.median(per_op(obs, set(spans))) * 1e3
