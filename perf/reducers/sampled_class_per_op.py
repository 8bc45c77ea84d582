"""Time a sampled thread stood in one class of its closed vocabulary, summed
per operation, then the median over the window's operations, in
milliseconds: ``span`` is ``<track>.<class>`` (``caller.transfer``), as the
program's watch (``tpusnap.telemetry.HolderWatch``) records it.

A class that no tick met reads 0 wherever the track was sampled at all (any
``<track>.*`` span in the window): the vocabulary is closed, a sampled
class is short as often as it is long, and a reading that comes and goes
with the sampler's luck would look like a metric the program lost. Where the
track has no span (a program from before the watch, a take that began with
no sink) there is nothing to read.
"""

import statistics

from perf.reducers.span_per_op import per_op


def reduce(obs, span):
    track = span.split(".")[0] + "."
    if not any(s["name"].startswith(track) for s in obs["spans"]):
        return None
    return statistics.median(per_op(obs, {span})) * 1e3
