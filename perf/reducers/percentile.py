"""A percentile of one of the traffic's series (step times and the like):
the smallest sample with at least ``q`` per cent of the samples at or
under it."""

import math


def reduce(obs, series, q):
    values = sorted(obs["series"].get(series) or [])
    if not values:
        return None
    return values[max(0, math.ceil(q / 100.0 * len(values)) - 1)]
