"""The named byte counters' growth per operation over the state's size,
median over the window's operations; nothing where the program never
bumped one of them (a program that lacks the counter gives no reading,
not a zero). A count, not a time."""

import statistics

from perf.reducers import counter_per_op


def reduce(obs, counters):
    names = set(counters)
    if not obs["ops"] or not any(c["name"] in names for c in obs["counters"]):
        return None
    return statistics.median(counter_per_op.per_op(obs, names)) / obs["state_bytes"]
