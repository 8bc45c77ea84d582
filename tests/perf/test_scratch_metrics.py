"""The two per-layer readings of the fs plug-in's scratch pool (PR 47):
``scratch_fresh_bytes_per_state_byte`` (c:``read.scratch_fresh_bytes``) and
``scratch_wait_ms`` (s:``read.scratch_wait``). Both are data files over
reducers the benchmark had; each loads, reads a synthetic ``obs`` to the
expected number, and reads nothing, without raising, from a program that
has no pool (the parent of this change laid under these files). CPU only."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PERF = os.path.join(ROOT, "perf")
sys.path.insert(0, ROOT)

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
CELLS = {w["name"]: w for w in MANIFEST["workloads"]}

# reading -> (reducer, source, unit, what it reads)
READINGS = {
    "scratch_fresh_bytes_per_state_byte": (
        "counted_bytes_per_state_byte", "program_counter", "ratio", "read.scratch_fresh_bytes"),
    "scratch_wait_ms": ("span_per_op", "program_span", "ms", "read.scratch_wait"),
}
NEW = [m for m in MANIFEST["per_layer"] if m["name"] in READINGS]

STATE = 1000
OPS = [{"t_call": 0.0, "t_done": 10.0}, {"t_call": 10.0, "t_done": 20.0},
       {"t_call": 20.0, "t_done": 30.0}]


def _read(reading, obs):
    from perf import harness

    spec = harness.layer_metric_spec(reading)
    return harness.load_module("reducers", spec["reducer"]).reduce(obs, **spec.get("args", {}))


def _obs(fresh_per_op, waits_per_op=((0.25, 0.5), (0.1,), (0.0, 0.0))):
    """Three restores. Each bumps the fresh counter in two steps and the
    reused one by the rest of the state; each records its readers' waits;
    one more of everything lies outside every restore."""
    counters, spans = [], []
    for op, fresh, waits in zip(OPS, fresh_per_op, waits_per_op):
        t = op["t_call"]
        counters += [{"name": "read.scratch_fresh_bytes", "t": t + 1, "delta": fresh - 7},
                     {"name": "read.scratch_fresh_bytes", "t": t + 2, "delta": 7},
                     {"name": "read.scratch_reused_bytes", "t": t + 3, "delta": STATE - fresh},
                     {"name": "storage.bytes_read", "t": t + 3, "delta": STATE}]
        for k, w in enumerate(waits):
            spans.append({"name": "read.scratch_wait", "start": t + 1 + k, "end": t + 1 + k + w,
                          "bytes": 0, "kind": "wait"})
        spans.append({"name": "read.work", "start": t + 4, "end": t + 9, "bytes": STATE,
                      "kind": "work"})
    counters.append({"name": "read.scratch_fresh_bytes", "t": 35.0, "delta": 999})
    spans.append({"name": "read.scratch_wait", "start": 35.0, "end": 36.0, "bytes": 0,
                  "kind": "wait"})
    return {"ops": OPS, "spans": spans, "counters": counters, "state_bytes": STATE}


@pytest.mark.parametrize("reading", sorted(READINGS))
def test_each_file_loads_and_names_a_reducer_the_benchmark_had(reading):
    from perf import harness

    reducer, _, _, reads = READINGS[reading]
    spec = harness.layer_metric_spec(reading)
    assert set(spec) <= {"reducer", "args", "doc", "count"}
    assert spec["reducer"] == reducer
    assert os.path.isfile(os.path.join(PERF, "reducers", f"{reducer}.py"))
    assert callable(harness.load_module("reducers", reducer).reduce)
    assert [reads] in spec["args"].values()
    # A count prints on the CPU too; a time never does.
    assert bool(spec.get("count")) == (reducer == "counted_bytes_per_state_byte")
    # The doc says what is read and on which thread it is recorded.
    assert reads in spec["doc"] and "reader thread" in spec["doc"] and len(spec["doc"]) > 80


@pytest.mark.parametrize("fresh, want", [
    ((1000, 1000, 1000), 1.0),   # every read a buffer of its own: the parent's behaviour, counted
    ((550, 400, 300), 0.4),      # the median restore
    ((300, 300, 1000), 0.3),     # one restore whose buffers did not come back
])
def test_the_fresh_share_is_the_counters_growth_over_the_state(fresh, want):
    assert _read("scratch_fresh_bytes_per_state_byte", _obs(fresh)) == pytest.approx(want)


def test_the_wait_is_summed_per_restore():
    assert _read("scratch_wait_ms", _obs((1000,) * 3)) == pytest.approx(100.0)
    # Every read records the span, also one that waited for nothing: a
    # restore without a wait reads 0, not nothing.
    assert _read("scratch_wait_ms", _obs((1000,) * 3, ((0.0,), (0.0,), (0.0,)))) == 0.0


@pytest.mark.parametrize("reading", sorted(READINGS))
def test_a_program_without_the_pool_gives_nothing_to_read(reading):
    """The parent of this change, and a cell whose window reads no blob of
    4 MiB or more into scratch: no such counter, no such span; the reader
    returns nothing and does not raise, and the line leaves the metric out."""
    reads = READINGS[reading][3]
    silent = _obs((1000,) * 3)
    silent["spans"] = [s for s in silent["spans"] if s["name"] != reads]
    silent["counters"] = [c for c in silent["counters"] if c["name"] != reads]
    assert _read(reading, silent) is None
    assert _read(reading, {"ops": [], "spans": [], "counters": [], "state_bytes": STATE}) is None


@pytest.mark.parametrize("reading", sorted(READINGS))
def test_the_entry_is_appended_for_the_resume_cell_alone(reading):
    _, source, unit, _ = READINGS[reading]
    (metric,) = [m for m in NEW if m["name"] == reading]
    assert metric == {"name": reading, "unit": unit, "better": "lower", "source": source,
                      "layer": "storage read", "moves": "resume_s",
                      "workloads": ["pythia-410m.resume"]}
    # A layer the benchmark already names, letter for letter; a cell that
    # reports what the reading moves.
    assert any(m["layer"] == metric["layer"] for m in MANIFEST["per_layer"] if m not in NEW)
    moved = next(m for m in MANIFEST["end_to_end"] if m["name"] == metric["moves"])
    assert all(c in moved.get("workloads", CELLS) for c in metric["workloads"])
    # Appended: behind every entry the benchmark had (PR 46's last among them).
    names = [m["name"] for m in MANIFEST["per_layer"]]
    assert names.index(reading) > names.index("mtp_share_of_step")
