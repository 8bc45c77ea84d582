"""The per-layer reading of the staging budget's waits (PR 52):
``budget_wait_ms`` (s:``budget_wait``). A data file over a reducer the
benchmark had; it loads, reads a synthetic ``obs`` to the expected number,
reads 0 from a take whose one span is empty (since PR 52 a take's staging
ends with a span that is empty where no wait was open), and reads nothing,
without raising, from a take that recorded none (the parent of this change
where it never waited). CPU only."""

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
READING = "budget_wait_ms"
SPAN = "budget_wait"

OPS = [{"t_call": 0.0, "t_done": 10.0}, {"t_call": 10.0, "t_done": 20.0},
       {"t_call": 20.0, "t_done": 30.0}]


def _read(obs):
    from perf import harness

    spec = harness.layer_metric_spec(READING)
    return harness.load_module("reducers", spec["reducer"]).reduce(obs, **spec.get("args", {}))


def _obs(waits_per_save):
    """Three saves; each holds the given episodes (seconds) and spans of
    other names; one more episode lies outside every save (the warm-up
    take's)."""
    spans = []
    for op, waits in zip(OPS, waits_per_save):
        t = op["t_call"] + 1.0
        for w in waits:
            spans.append({"name": SPAN, "start": t, "end": t + w, "kind": "wait"})
            t += w + 0.1
        spans.append({"name": "stage.work", "start": t, "end": t + 0.7, "kind": "work"})
    spans.append({"name": SPAN, "start": 31.0, "end": 33.0, "kind": "wait"})
    return {"ops": OPS, "spans": spans, "counters": [], "state_bytes": 1000}


def test_the_file_loads_and_names_a_reducer_the_benchmark_had():
    from perf import harness

    spec = harness.layer_metric_spec(READING)
    assert set(spec) <= {"reducer", "args", "doc", "count"}
    assert spec["reducer"] == "span_per_op"
    assert os.path.isfile(os.path.join(PERF, "reducers", "span_per_op.py"))
    assert spec["args"] == {"spans": [SPAN]}
    assert not spec.get("count")  # a time: never printed from the CPU
    # The doc says what the span covers, where it is recorded and what silence means.
    for said in (SPAN, "loop's thread", "fsync", "reads 0", "Nothing to read"):
        assert said in spec["doc"], said


@pytest.mark.parametrize("waits, want_ms", [
    (([0.4, 0.6], [1.2], [0.2, 0.3, 0.4]), 1000.0),  # the median save: 1.0 s in two episodes
    (([1.1], [0.0], [0.0]), 0.0),  # two saves whose one span is empty: they never waited
    (([0.0], [0.0], [0.0]), 0.0),  # a take that never waited reads 0, not nothing
])
def test_the_reading_is_the_episodes_sum_a_save(waits, want_ms):
    assert _read(_obs(waits)) == pytest.approx(want_ms)


def test_a_program_that_records_no_such_span_gives_nothing_to_read():
    """Before PR 52 a take that never waited recorded none: the reader
    returns nothing and does not raise, and the line leaves the metric out."""
    silent = _obs(([0.5], [0.5], [0.5]))
    silent["spans"] = [s for s in silent["spans"] if s["name"] != SPAN]
    assert _read(silent) is None
    assert _read({"ops": [], "spans": [], "counters": [], "state_bytes": 1000}) is None


def test_the_entry_is_appended_for_the_one_chip_cells_that_save():
    (metric,) = [m for m in MANIFEST["per_layer"] if m["name"].split(".")[0] == READING]
    flagship = next(m for m in MANIFEST["per_layer"] if m["name"] == "stage_queue_ms")
    assert metric == {"name": READING, "unit": "ms", "better": "lower",
                      "source": "program_span", "layer": "stage/hash",
                      "moves": "train_tokens_per_s", "workloads": flagship["workloads"]}
    # The layer that holds the scheduler's other readings, letter for
    # letter; one-chip cells that take a snapshot in their window and no
    # other (the four-chip cell's budget is its own reading's to name).
    assert all(CELLS[c]["traffic"].startswith("save_loop") and CELLS[c]["chips"] == 1
               for c in metric["workloads"])
    moved = next(m for m in MANIFEST["end_to_end"] if m["name"] == metric["moves"])
    assert set(metric["workloads"]) <= set(moved["workloads"])
    # Appended: behind every entry the benchmark had (PR 51's last among them).
    names = [m["name"] for m in MANIFEST["per_layer"]]
    assert names.index(READING) > names.index("dtoh_owned_bytes_per_state_byte")
