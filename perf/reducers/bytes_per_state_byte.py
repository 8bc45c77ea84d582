"""Bytes moved per byte of state, per operation: the named counters'
growth plus the named spans' ``bytes``, over the state's size; median
over the window's operations. A count, not a time."""

import statistics

from perf.reducers import counter_per_op, span_per_op


def reduce(obs, counters=(), spans=()):
    if not obs["ops"] or not (obs["spans"] or obs["counters"]):
        return None
    counted = counter_per_op.per_op(obs, set(counters))
    spanned = span_per_op.per_op(obs, set(spans), "bytes")
    return statistics.median(c + s for c, s in zip(counted, spanned)) / obs["state_bytes"]
