"""A counter's growth per operation, median over the window's operations."""

import statistics


def per_op(obs, names):
    out = []
    for op in obs["ops"]:
        out.append(sum(
            c["delta"] for c in obs["counters"]
            if c["name"] in names and op["t_call"] <= c["t"] <= op["t_done"]
        ))
    return out


def reduce(obs, counters):
    if not obs["ops"] or not obs["spans"]:
        return None  # no sink was listening
    return statistics.median(per_op(obs, set(counters)))
