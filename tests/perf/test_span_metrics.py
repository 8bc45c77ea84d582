"""The per-layer metrics that read the program's wait/work spans and its
annotations in the profiler's trace: each new reducer on a hand-made
``obs``, the idle attribution on a synthetic plane set, the manifest's new
entries, and ``perf/run.py --rehearsal`` printing the new count. CPU only."""

import json
import os
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PERF = os.path.join(ROOT, "perf")
sys.path.insert(0, ROOT)

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
READINGS = (
    "blocked_prepare_ms", "blocked_stage_ms", "dtoh_window_ms", "stage_work_ms",
    "stage_queue_ms", "write_work_ms", "write_fsync_ms", "write_queue_ms",
    "stored_bytes_per_state_byte", "host_rss_per_state_byte", "read_work_ms",
    "read_queue_ms", "decode_busy_ms", "htod_busy_ms", "idle_unexplained_share",
)
NEW = [m for m in MANIFEST["per_layer"] if m["name"].split(".")[0] in READINGS]


def span(name, start, end, nbytes=0):
    return {"name": name, "start": start, "end": end, "bytes": nbytes}


OPS = [{"t_call": 0.0, "t_done": 10.0}, {"t_call": 10.0, "t_done": 20.0}]


def test_span_union_counts_overlapping_seconds_once():
    from perf.reducers import span_union_per_op

    obs = {"ops": OPS, "spans": [
        span("dtoh.transfer", 1.0, 3.0), span("dtoh.transfer", 2.0, 4.0),  # 3 s, not 4
        span("dtoh.transfer", 6.0, 7.0), span("dtoh", 0.0, 9.0),           # + 1 s
        span("dtoh.transfer", 11.0, 12.5), span("dtoh.transfer", 11.5, 12.0),  # 1.5 s
    ]}
    assert span_union_per_op.per_op(obs, {"dtoh.transfer"}) == [4.0, 1.5]
    assert span_union_per_op.reduce(obs, ["dtoh.transfer"]) == pytest.approx(2750.0)
    assert span_union_per_op.reduce(obs, ["no_such_span"]) is None
    assert span_union_per_op.reduce({"ops": [], "spans": obs["spans"]}, ["dtoh"]) is None


@pytest.mark.parametrize(
    "name", [m["name"] for m in MANIFEST["per_layer"]
             if m["name"].split(".")[0] == "dtoh_bytes_per_state_byte"])
def test_dtoh_bytes_count_each_crossing_once(name):
    """A save of 1000 bytes: a prefetched leaf of 600 (its copy is started
    at prepare time and counted there; its ``dtoh`` span is the residual
    wait), two slab members of 100 each (prefetched, then fetched again
    inside the packed slab: two crossings, S2), and a leaf of 200 behind a
    transform (not prefetched: its blocking fetch is its one crossing)."""
    from perf import harness

    def leaf(name, start, end, nbytes, kind):
        return {**span(name, start, end, nbytes), "kind": kind}

    obs = {
        "ops": OPS[:1], "state_bytes": 1000,
        "counters": [{"name": "dtoh.enqueued_bytes", "t": 0.1, "delta": n}
                     for n in (600, 100, 100)],
        "spans": [
            leaf("dtoh", 1.0, 1.5, 600, "wait"), leaf("dtoh.transfer", 0.1, 1.5, 600, "work"),
            leaf("dtoh", 2.0, 2.1, 200, "work"), leaf("dtoh.transfer", 2.0, 2.1, 200, "work"),
            leaf("dtoh", 3.0, 3.2, 200, "work"), leaf("dtoh.transfer", 3.0, 3.2, 200, "work"),
            leaf("dtoh", 0.2, 0.4, 0, "wait"),  # the codec policy's wait for its sample
        ],
    }
    spec = harness.layer_metric_spec(name)
    reducer = harness.load_module("reducers", spec["reducer"])
    assert spec["count"] and reducer.reduce(obs, **spec["args"]) == pytest.approx(1.2)
    # What the file read before: every prefetched byte twice.
    assert reducer.reduce(obs, counters=["dtoh.enqueued_bytes"],
                          spans=["dtoh"]) == pytest.approx(1.8)
    # All of a state prefetched and nothing packed: what crosses is 1.0.
    plain = {**obs, "counters": [{"name": "dtoh.enqueued_bytes", "t": 0.1, "delta": 1000}],
             "spans": [leaf("dtoh", 1.0, 1.5, 1000, "wait"),
                       leaf("dtoh.transfer", 0.1, 1.5, 1000, "work")]}
    assert reducer.reduce(plain, **spec["args"]) == pytest.approx(1.0)


def test_queue_work_and_fsync_add_up_per_save():
    """``span_per_op`` on the hand-off's spans: the three parts of a
    write's time, each under its own metric's file."""
    from perf import harness
    from perf.reducers import span_per_op

    obs = {"ops": OPS[:1], "spans": [
        span("storage_write", 0.0, 5.0),
        span("write.queued", 0.0, 1.0), span("write.work", 1.0, 4.0),
        span("write.queued", 4.0, 4.25), span("write.fsync", 4.25, 5.0),
    ]}
    parts = {
        name: span_per_op.reduce(obs, **harness.layer_metric_spec(name)["args"])
        for name in ("write_queue_ms", "write_work_ms", "write_fsync_ms", "write_busy_ms")
    }
    assert parts == {"write_queue_ms": 1250.0, "write_work_ms": 3000.0,
                     "write_fsync_ms": 750.0, "write_busy_ms": 5000.0}
    assert span_per_op.reduce(obs, **harness.layer_metric_spec("htod_busy_ms")["args"]) is None


def test_take_gauge_reads_the_last_takes_summary(monkeypatch):
    from perf.reducers import take_gauge_per_state_byte
    from tpusnap import telemetry

    obs = {"ops": OPS, "state_bytes": 1000}
    monkeypatch.setattr(telemetry, "LAST_TAKE_SUMMARY", {"gauges": {"peak_rss_delta_bytes": 250.0}})
    assert take_gauge_per_state_byte.reduce(obs, "peak_rss_delta_bytes") == 0.25
    assert take_gauge_per_state_byte.reduce(obs, "no_such_gauge") is None
    assert take_gauge_per_state_byte.reduce({**obs, "ops": []}, "peak_rss_delta_bytes") is None
    monkeypatch.setattr(telemetry, "LAST_TAKE_SUMMARY", None)
    assert take_gauge_per_state_byte.reduce(obs, "peak_rss_delta_bytes") is None


def test_a_gap_half_under_an_annotation_reads_fifty_per_cent():
    from perf.reducers import trace_unexplained_idle as tui

    busy = [[0.0, 1.0], [3.0, 4.0], [4.0005, 5.0]]  # one gap of 2 s; 0.5 ms is no gap
    assert tui.idle_gaps(busy) == [(1.0, 3.0)]
    by_leaf = tui.attribute(tui.idle_gaps(busy), [(1, 0.5, 2.0, "prepare")])
    assert by_leaf == {"prepare": 1.0, tui.NONE: 1.0}


def test_idle_goes_to_the_dispatching_threads_innermost_annotation():
    from perf.reducers import trace_unexplained_idle as tui

    annotations = [
        (1, 0.0, 10.0, "stage"),          # the dispatching thread's phase
        (1, 2.0, 3.0, "comm.barrier"),    # nested in it on that thread
        (2, 0.0, 4.0, "stage.work"),      # a worker's, shorter than the phase
        (2, 11.0, 12.0, "write.work"),    # a worker's, where thread 1 has none
    ]
    gaps = [(1.0, 4.0), (10.5, 13.0)]
    by_leaf = tui.attribute(gaps, annotations, main_thread=1)
    assert by_leaf == pytest.approx({"stage": 2.0, "comm.barrier": 1.0,
                                     "write.work": 1.0, tui.NONE: 1.5})
    # With no anchor no thread comes first: the innermost of any thread's.
    by_leaf = tui.attribute(gaps[:1], annotations)
    assert by_leaf == pytest.approx({"stage.work": 2.0, "comm.barrier": 1.0})


def _fake_profile(planes):
    def line(name, events):
        return types.SimpleNamespace(name=name, events=[
            types.SimpleNamespace(name=n, start_ns=s * 1e9, duration_ns=(e - s) * 1e9)
            for n, s, e in events
        ])
    return types.SimpleNamespace(planes=[
        types.SimpleNamespace(name=pname, lines=[line(*ln) for ln in lines])
        for pname, lines in planes
    ])


def test_the_reducer_finds_the_trace_beside_the_telemetry_dir(tmp_path, monkeypatch, capsys):
    """The harness exports ``<work_dir>/telemetry`` and traces into
    ``<work_dir>/trace``; the planes are a synthetic set."""
    import jax.profiler

    from perf.reducers import trace_unexplained_idle as tui

    run_dir = tmp_path / "trace" / "plugins" / "profile" / "2026_01_01"
    run_dir.mkdir(parents=True)
    (run_dir / "host.xplane.pb").write_bytes(b"")
    monkeypatch.setenv("TPUSNAP_TELEMETRY_DIR", str(tmp_path / "telemetry"))
    planes = [
        ("/device:TPU:1", [("XLA Ops", [("fusion", 0.0, 100.0)])]),  # not device 0
        ("/device:TPU:0", [("Steps", [("1", 0.0, 9.0)]),
                           ("XLA Ops", [("fusion.1", 0.0, 1.0), ("fusion.2", 5.0, 6.0),
                                        ("copy", 5.2, 5.4), ("fusion.3", 8.0, 9.0)])]),
        ("/host:CPU", [("python", [("perf_anchor", 0.0, 0.001), ("tpusnap:prepare", 1.0, 3.0),
                                   ("PjitFunction(step)", 4.0, 4.1)]),
                       ("python", [("tpusnap:stage.work", 2.5, 4.0)])]),
    ]
    monkeypatch.setattr(jax.profiler.ProfileData, "from_file",
                        staticmethod(lambda path: _fake_profile(planes)))
    share = tui.reduce({"trace": {"window_s": 9.0, "busy_s": 3.0}})
    # Gaps 1-5 and 6-8: prepare 2 s (the dispatching thread's, over the
    # worker's where both cover), stage.work 1 s, nothing 3 s of 6 s.
    assert share == pytest.approx(50.0)
    line = next(ln for ln in capsys.readouterr().out.splitlines()
                if ln.startswith("perf idle_by_leaf: "))
    table = json.loads(line.split(": ", 1)[1])
    assert table["anchored"] and table["idle_s"] == pytest.approx(6.0)
    assert dict(table["by_leaf"]) == pytest.approx(
        {tui.NONE: 3.0, "prepare": 2.0, "stage.work": 1.0})
    # A program that writes no annotation (the parent of this change), a
    # rehearsal without a device plane, or no trace: nothing to read.
    silent = [(p, [(ln, [e for e in evs if not e[0].startswith("tpusnap:")])
                   for ln, evs in lines]) for p, lines in planes]
    monkeypatch.setattr(jax.profiler.ProfileData, "from_file",
                        staticmethod(lambda path: _fake_profile(silent)))
    assert tui.reduce({"trace": {"window_s": 9.0}}) is None
    assert tui.reduce({"trace": None}) is None
    monkeypatch.setenv("TPUSNAP_TELEMETRY_DIR", str(tmp_path / "elsewhere" / "telemetry"))
    assert tui.reduce({"trace": {"window_s": 9.0}}) is None


@pytest.mark.manifest_shape
@pytest.mark.parametrize("metric", NEW, ids=lambda m: m["name"])
def test_new_entry_resolves_to_a_file_and_a_reducer(metric):
    from perf import harness

    spec = harness.layer_metric_spec(metric["name"])
    reducer = harness.load_module("reducers", spec["reducer"])
    assert callable(reducer.reduce) and spec["doc"]
    assert metric["name"].split(".")[0] in READINGS
    cells = {w["name"]: w for w in MANIFEST["workloads"]}
    four_chip = {cells[c]["chips"] == 4 for c in metric["workloads"]}
    if metric["name"].endswith(".sharded"):
        assert four_chip == {True} and metric["moves"] == "train_tokens_per_s"
    # Entries are appended: every one of PR 24's comes before the first new one.
    names = [m["name"] for m in MANIFEST["per_layer"]]
    assert names.index(metric["name"]) >= names.index("dtoh_bytes_per_state_byte.sharded") + 1


@pytest.mark.manifest_shape
def test_the_new_entries_are_the_table_of_the_issue():
    assert len(NEW) >= 23  # a later configuration's cells append the same readings under their own suffix
    assert sorted({m["name"].split(".")[0] for m in NEW}) == sorted(READINGS)
    assert sorted(os.listdir(os.path.join(PERF, "layer_metrics"))) == sorted(
        {f"{m['name'].split('.')[0]}.json" for m in MANIFEST["per_layer"]})


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_rehearsal_prints_the_new_count_and_no_time(tmp_path, cell):
    chips = next(w["chips"] for w in MANIFEST["workloads"] if w["name"] == cell)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    proc = subprocess.run(
        [sys.executable, os.path.join(PERF, "run.py"), "--workload", cell, "--seed",
         "2600000007", "--seconds", "2", "--trace", "1", "--rehearsal"],
        capture_output=True, text=True, timeout=400, cwd=ROOT, env=env)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"], proc.stdout[-3000:]
    mine = {m["name"]: m for m in NEW if cell in m["workloads"]}
    printed = {k: v["value"] for k, v in result["metrics"].items() if k in mine}
    counts = {k: v for k, v in printed.items() if v is not None}
    if any(k.startswith("stored_bytes_per_state_byte") for k in mine):
        assert list(counts.values()) == [1.0] and len(printed) >= 8, printed
    else:  # the resume cell writes nothing in its window: times alone, as null
        assert not counts and {"decode_busy_ms", "htod_busy_ms"} <= set(printed), printed
