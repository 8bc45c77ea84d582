"""The per-layer reading of the owned crossing (PR 51):
``dtoh_owned_bytes_per_state_byte`` (c:``dtoh.owned_bytes``). A data file over
a reducer the benchmark had; it loads, reads a synthetic ``obs`` to the
expected number, and reads nothing, without raising, from a program that
never copies a leaf for its crossing (the parent of this change laid under
this file, and any run on a CPU backend). CPU only."""

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
READING = "dtoh_owned_bytes_per_state_byte"
COUNTER = "dtoh.owned_bytes"

STATE = 1000
OPS = [{"t_call": 0.0, "t_done": 10.0}, {"t_call": 10.0, "t_done": 20.0},
       {"t_call": 20.0, "t_done": 30.0}]


def _read(obs):
    from perf import harness

    spec = harness.layer_metric_spec(READING)
    return harness.load_module("reducers", spec["reducer"]).reduce(obs, **spec.get("args", {}))


def _obs(owned_per_save):
    """Three saves. Each bumps the owned counter leaf by leaf and the
    crossings' own counter by the whole state; one more owned leaf lies
    outside every save (the warm-up take's)."""
    counters = []
    for op, owned in zip(OPS, owned_per_save):
        t = op["t_call"]
        counters += [{"name": COUNTER, "t": t + 1, "delta": owned - 7},
                     {"name": COUNTER, "t": t + 2, "delta": 7},
                     {"name": "dtoh.owned_leaves", "t": t + 2, "delta": 2},
                     {"name": "dtoh.enqueued_bytes", "t": t + 3, "delta": STATE}]
    counters.append({"name": COUNTER, "t": 35.0, "delta": 999})
    return {"ops": OPS, "spans": [], "counters": counters, "state_bytes": STATE}


def test_the_file_loads_and_names_a_reducer_the_benchmark_had():
    from perf import harness

    spec = harness.layer_metric_spec(READING)
    assert set(spec) <= {"reducer", "args", "doc", "count"}
    assert spec["reducer"] == "counted_bytes_per_state_byte"
    assert os.path.isfile(os.path.join(PERF, "reducers", "counted_bytes_per_state_byte.py"))
    assert spec["args"] == {"counters": [COUNTER]}
    assert spec["count"] is True  # a count prints on the CPU too, where it has something to read
    # The doc says what is counted, where it is bumped and what silence means.
    assert COUNTER in spec["doc"] and "thread" in spec["doc"] and "never a zero" in spec["doc"]


@pytest.mark.parametrize("owned, want", [
    ((1000, 1000, 1000), 1.0),  # every byte of the state crossed from a copy of tpusnap's own
    ((990, 930, 1000), 0.99),   # the median save
    ((930, 930, 0), 0.93),      # one save whose leaves all fell back
])
def test_the_share_is_the_counters_growth_over_the_state(owned, want):
    assert _read(_obs(owned)) == pytest.approx(want)


def test_a_program_that_copies_nothing_gives_nothing_to_read():
    """The parent of this change and a CPU backend: no such counter; the
    reader returns nothing and does not raise, and the line leaves the
    metric out."""
    silent = _obs((1000,) * 3)
    silent["counters"] = [c for c in silent["counters"] if c["name"] != COUNTER]
    assert _read(silent) is None
    assert _read({"ops": [], "spans": [], "counters": [], "state_bytes": STATE}) is None


def test_the_entry_is_appended_for_the_cells_that_save():
    (metric,) = [m for m in MANIFEST["per_layer"] if m["name"].split(".")[0] == READING]
    moved = next(m for m in MANIFEST["end_to_end"] if m["name"] == "train_tokens_per_s")
    assert metric == {"name": READING, "unit": "ratio", "better": "higher",
                      "source": "program_counter", "layer": "plan/prepare and DtoH",
                      "moves": "train_tokens_per_s", "workloads": moved["workloads"]}
    # Every one-chip save cell is listed wherever the first is
    # (tests/perf/test_harness.py), the donated ones too; there the caller
    # stands in wait_staged() and gets no copy made for it, and the file's
    # doc says that a low share is the rule at work in those cells.
    from perf import harness

    doc = harness.layer_metric_spec(READING)["doc"]
    assert "wait_staged()" in doc and "LOW share" in doc
    # A layer the benchmark already names, letter for letter; every cell
    # that takes a snapshot in its window, and no cell that only restores.
    assert any(m["layer"] == metric["layer"] for m in MANIFEST["per_layer"] if m is not metric)
    assert all(CELLS[c]["traffic"].startswith("save_loop") for c in metric["workloads"])
    # Appended: behind every entry the benchmark had (PR 49's last among them).
    names = [m["name"] for m in MANIFEST["per_layer"]]
    assert names.index(READING) > names.index("ssm_scan_share_of_step")
