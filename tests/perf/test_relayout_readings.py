"""What the program says of a relayout (s:``relayout``, c:``stage.relayouts``,
c:``stage.relayout_bytes``; PR 32) as the benchmark reads it since PR 34:
the two metric files ``relayout_ms`` and ``relayout_bytes_per_state_byte``,
their four entries in the manifest, and the reducers they name
(``span_per_op``, ``counted_bytes_per_state_byte``) on a hand-made
observation and on what a real take gives the harness's own sink. CPU only."""

import json
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perf import harness  # noqa: E402
from perf.reducers import counted_bytes_per_state_byte, counter_per_op, span_per_op  # noqa: E402
from tpusnap import Snapshot, StateDict, metrics_sink  # noqa: E402
from tpusnap.serialization import RELAYOUT_MIN_BYTES  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
READINGS = {"relayout_ms": ("ms", "program_span"),
            "relayout_bytes_per_state_byte": ("ratio", "program_counter")}
ENTRIES = [m for m in MANIFEST["per_layer"] if m["name"].split(".")[0] in READINGS]
# The span and the counter are the ones the metric files name.
SPANS = harness.layer_metric_spec("relayout_ms")["args"]["spans"]
COUNTERS = harness.layer_metric_spec("relayout_bytes_per_state_byte")["args"]["counters"]


@pytest.mark.manifest_shape
def test_the_metric_files_name_the_programs_span_and_counter():
    assert SPANS == ["relayout"] and COUNTERS == ["stage.relayout_bytes"]
    ms, share = (harness.layer_metric_spec(name) for name in READINGS)
    assert (ms["reducer"], share["reducer"]) == ("span_per_op", "counted_bytes_per_state_byte")
    assert not ms.get("count") and share["count"] is True  # a time is never printed from the CPU


@pytest.mark.manifest_shape
@pytest.mark.parametrize("entry", ENTRIES, ids=lambda m: m["name"])
def test_an_entry_reads_a_cell_whose_state_has_a_leaf_to_turn(entry):
    """A reading lists the cells with such a leaf, one or more: the sparse
    cell's head and its moments, and on four chips the ``.sharded`` twin for
    the sharded head's. The dense one-chip cells have none (50,304 is
    393 x 128) and are in no list."""
    reading = entry["name"].split(".")[0]
    assert (entry["unit"], entry["source"]) == READINGS[reading]
    assert (entry["layer"], entry["moves"], entry["better"]) == (
        "stage/hash", "train_tokens_per_s", "lower")
    cells = {w["name"]: w for w in MANIFEST["workloads"]}
    assert len(entry["workloads"]) >= 1
    for listed in entry["workloads"]:
        assert cells[listed]["chips"] == (4 if entry["name"].endswith(".sharded") else 1)
        assert cells[listed]["config"] != "pythia-410m"
    spec = harness.layer_metric_spec(entry["name"])
    assert callable(harness.load_module("reducers", spec["reducer"]).reduce) and spec["doc"]
    # Appended: after every entry the manifest had at PR 32.
    names = [m["name"] for m in MANIFEST["per_layer"]]
    assert names.index(entry["name"]) > names.index("attn_share_of_step")


@pytest.mark.manifest_shape
def test_both_readings_have_their_one_chip_and_their_four_chip_entry():
    assert sorted(m["name"] for m in ENTRIES) == sorted(
        [r for r in READINGS] + [f"{r}.sharded" for r in READINGS])


def _handmade(leaves, saves=2, state_bytes=1000):
    """``saves`` saves ten seconds apart; in each, every leaf is turned
    in 0.1 s inside one ``stage.work`` of its own."""
    ops, spans, counters = [], [], []
    for k in range(saves):
        t0 = 10.0 * k
        ops.append({"t_call": t0, "t_done": t0 + 10.0})
        for i, nbytes in enumerate(leaves):
            start = t0 + 1 + i
            spans += [{"name": "stage.work", "start": start - 0.2, "end": start + 0.5, "bytes": 0, "kind": "work"},
                      {"name": "relayout", "start": start, "end": start + 0.1, "bytes": nbytes, "kind": "work"}]
            counters += [{"name": "stage.relayouts", "t": start + 0.1, "delta": 1},
                         {"name": "stage.relayout_bytes", "t": start + 0.1, "delta": nbytes}]
    return {"ops": ops, "spans": spans, "counters": counters, "state_bytes": state_bytes}


@pytest.mark.parametrize("leaves, ms, share", [
    ((50, 50, 58), 300.0, 0.158),   # three leaves a save, as the sparse cell's head and its moments
    ((200,), 100.0, 0.2),
], ids=["three_leaves", "one_leaf"])
def test_the_reducers_give_the_sum_and_the_share(leaves, ms, share):
    obs = _handmade(leaves)
    assert span_per_op.reduce(obs, SPANS) == pytest.approx(ms)
    assert counted_bytes_per_state_byte.reduce(obs, COUNTERS) == pytest.approx(share)
    assert counter_per_op.reduce(obs, ["stage.relayouts"]) == len(leaves)
    assert span_per_op.per_op(obs, set(SPANS), "bytes") == [sum(leaves)] * 2


def test_a_program_that_turns_nothing_gives_nothing_to_read():
    """The parent of PR 32, and every take of a state without a strided
    leaf: no span and no counter, so no reading, not a zero."""
    obs = _handmade(())
    obs["spans"].append({"name": "stage.work", "start": 1.0, "end": 2.0, "bytes": 0, "kind": "work"})
    assert span_per_op.reduce(obs, SPANS) is None
    assert counted_bytes_per_state_byte.reduce(obs, COUNTERS) is None
    assert counter_per_op.reduce(obs, ["stage.relayouts"]) == 0


@pytest.mark.parametrize("how", ["take", "async_take"])
def test_a_real_take_read_through_the_harness_sink(tmp_path, monkeypatch, how):
    monkeypatch.setenv("TPUSNAP_TELEMETRY", "1")
    rng = np.random.default_rng(32)
    turned = np.asfortranarray(rng.standard_normal((1500, 1500), dtype=np.float32))
    plain = rng.standard_normal((1500, 1500), dtype=np.float32)
    assert turned.nbytes >= RELAYOUT_MIN_BYTES
    app = {"m": StateDict(turned=turned, plain=plain)}
    log = harness.SpanLog()
    t_call = time.monotonic()
    with metrics_sink(log):
        if how == "take":
            Snapshot.take(str(tmp_path / "snap"), app)
        else:
            Snapshot.async_take(str(tmp_path / "snap"), app).wait()
    obs = {"ops": [{"t_call": t_call, "t_done": time.monotonic()}], "spans": log.spans,
           "counters": log.counters, "state_bytes": turned.nbytes + plain.nbytes}
    assert span_per_op.reduce(obs, SPANS) > 0
    assert span_per_op.per_op(obs, set(SPANS), "bytes", "work") == [turned.nbytes]
    assert counted_bytes_per_state_byte.reduce(obs, COUNTERS) == pytest.approx(0.5)
    assert counter_per_op.reduce(obs, ["stage.relayouts"]) == 1
