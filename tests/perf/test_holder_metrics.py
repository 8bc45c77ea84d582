"""The per-layer metrics that read what kept the caller's thread and the
event loop from running (the ``.resumed`` spans, the ``caller.*`` /
``loop.*`` spans, the watch's counters, the ``tpusnap-caller:`` annotations):
every new file loads, names a reducer that exists and reads a synthetic
``obs`` to the expected number; the idle seconds by the caller's class on
hand-made gaps and on a synthetic plane set; the manifest's new entries;
and ``perf/run.py --rehearsal`` of every cell still printing its line. CPU
only."""

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
CELLS = {w["name"]: w for w in MANIFEST["workloads"]}

# reading -> (reducer, what it reads, the number the synthetic obs gives)
SPAN_SUMS = {
    "write_resume_ms": ("write.resumed", 700.0),
    "read_resume_ms": ("read.resumed", 1500.0),
    "stage_resume_ms": ("stage.resumed", 250.0),
    "consume_resume_ms": ("consume.resumed", 125.0),
    "consume_queue_ms": ("consume.queued", 2000.0),
    "loop_callback_ms": ("loop.callback", 600.0),
}
# A class of the caller's closed vocabulary (`sampled_class_per_op`).
SAMPLED = {
    "caller_other_ms": ("caller.other", 400.0),
    "caller_wait_device_ms": ("caller.wait_device", 1000.0),
    "caller_transfer_ms": ("caller.transfer", 50.0),
}
COUNTERS = {
    "caller_runq_us": ("caller.runq_us", 1200),
    "loop_runq_us": ("loop.runq_us", 800),
    "watch_late_us": ("watch.late_us", 4100),
    "process_cpu_us": ("take.process_cpu_us", 16_000_000),
}
READINGS = [*SPAN_SUMS, *SAMPLED, *COUNTERS, "loop_late_window_ms",
            "idle_caller_waiting_ms", "idle_caller_elsewhere_ms"]
NEW = [m for m in MANIFEST["per_layer"] if m["name"].split(".")[0] in READINGS]

OPS = [{"t_call": 0.0, "t_done": 10.0}, {"t_call": 10.0, "t_done": 20.0}]


def span(name, start, end):
    return {"name": name, "start": start, "end": end, "bytes": 0, "kind": "wait"}


def _obs():
    """Two operations. In each, every span that a reading sums fires twice
    (its number split 3:1) and once more outside both operations; every
    counter grows in two steps; the four ``.resumed`` names overlap so that
    their union is shorter than their sum."""
    spans, counters = [], []
    for op in OPS:
        t = op["t_call"]
        for name, total_ms in [*SPAN_SUMS.values(), *SAMPLED.values()]:
            total = total_ms / 1e3
            spans += [span(name, t + 1, t + 1 + 0.75 * total), span(name, t + 4, t + 4 + 0.25 * total)]
        for name, total in COUNTERS.values():
            counters += [{"name": name, "t": t + 2, "delta": total - 7},
                         {"name": name, "t": t + 3, "delta": 7}]
        spans.append(span("storage_write", t, t + 9))
    spans += [span(name, 25.0, 26.0) for name, _ in [*SPAN_SUMS.values(), *SAMPLED.values()]]
    counters += [{"name": name, "t": 25.0, "delta": 99} for name, _ in COUNTERS.values()]
    return {"ops": OPS, "spans": spans, "counters": counters, "state_bytes": 1}


def _read(reading, obs):
    from perf import harness

    spec = harness.layer_metric_spec(reading)
    return harness.load_module("reducers", spec["reducer"]).reduce(obs, **spec.get("args", {}))


@pytest.mark.parametrize("reading", READINGS)
def test_every_new_file_loads_and_names_a_reducer_that_exists(reading):
    from perf import harness

    spec = harness.layer_metric_spec(reading)
    assert set(spec) <= {"reducer", "args", "doc", "count"} and not spec.get("count")
    assert os.path.isfile(os.path.join(PERF, "reducers", f"{spec['reducer']}.py"))
    assert callable(harness.load_module("reducers", spec["reducer"]).reduce)
    # The doc says what is read and on which thread it is recorded.
    assert "thread" in spec["doc"] and len(spec["doc"]) > 80
    # A split reading has one file: the suffixed names find the same one.
    for suffix in (".save", ".resume", ".sharded"):
        assert harness.layer_metric_spec(reading + suffix) == spec


@pytest.mark.parametrize("reading", sorted(SPAN_SUMS))
def test_a_span_reading_sums_its_span_per_operation(reading):
    name, want_ms = SPAN_SUMS[reading]
    assert _read(reading, _obs()) == pytest.approx(want_ms)
    # A program that records no such span (the parent of this change; a run
    # whose take began with no sink): nothing to read, and nothing raised.
    silent = _obs()
    silent["spans"] = [s for s in silent["spans"] if s["name"] != name]
    assert _read(reading, silent) is None


@pytest.mark.parametrize("reading", sorted(SAMPLED))
def test_a_sampled_class_reads_zero_where_no_tick_met_it(reading):
    name, want_ms = SAMPLED[reading]
    assert _read(reading, _obs()) == pytest.approx(want_ms)
    # The caller was sampled and never seen in this class (a donating loop
    # stands in `wait_staged`; a `device_put` of 0.2 ms between ticks of 5):
    # the reading is 0, not gone.
    unseen = _obs()
    unseen["spans"] = [s for s in unseen["spans"] if s["name"] != name]
    assert _read(reading, unseen) == 0
    # No caller's track at all (the parent of this change; no sink): nothing.
    silent = _obs()
    silent["spans"] = [s for s in silent["spans"] if not s["name"].startswith("caller.")]
    assert _read(reading, silent) is None


@pytest.mark.parametrize("reading", sorted(COUNTERS))
def test_a_counter_reading_is_the_counters_growth_per_operation(reading):
    name, want = COUNTERS[reading]
    assert _read(reading, _obs()) == want
    # No sink was listening: nothing. A sink and no such counter (the
    # parent; a kernel without schedstat): the counter grew by nothing.
    assert _read(reading, {"ops": OPS, "spans": [], "counters": []}) is None
    silent = _obs()
    silent["counters"] = [c for c in silent["counters"] if c["name"] != name]
    assert _read(reading, silent) == 0


def test_the_late_window_is_the_union_of_the_four_resumed_names():
    obs = {"ops": OPS[:1], "spans": [
        span("write.resumed", 1.0, 2.0), span("stage.resumed", 1.5, 2.5),   # 1.5 s, not 2
        span("read.resumed", 4.0, 4.25), span("consume.resumed", 4.0, 4.5),  # 0.5 s
        span("write.queued", 0.0, 9.0), span("loop.callback", 6.0, 7.0),     # not of the four
    ]}
    assert _read("loop_late_window_ms", obs) == pytest.approx(2000.0)
    assert _read("loop_late_window_ms.resume", obs) == pytest.approx(2000.0)
    assert _read("loop_late_window_ms", {"ops": OPS[:1], "spans": obs["spans"][4:]}) is None


def test_the_await_sum_closes_with_the_resumed_leg():
    """What the acceptance asks of a traced run, on hand-made spans: the
    await span less its queue, work, fsync and resumed legs is what the
    coroutine itself ran between its trips."""
    obs = {"ops": OPS[:1], "spans": [
        span("storage_write", 0.0, 5.0),
        span("write.queued", 0.0, 0.5), span("write.work", 0.5, 3.0), span("write.resumed", 3.0, 3.4),
        span("write.queued", 3.5, 3.6), span("write.fsync", 3.6, 4.6), span("write.resumed", 4.6, 4.95),
    ]}
    parts = {n: _read(n, obs) for n in ("write_busy_ms", "write_queue_ms", "write_work_ms",
                                        "write_fsync_ms", "write_resume_ms")}
    residue = parts.pop("write_busy_ms") - sum(parts.values())
    assert parts["write_resume_ms"] == pytest.approx(750.0)
    assert residue == pytest.approx(150.0) and residue < 0.05 * 5000.0


# ---- idle seconds by where the caller stood


def test_idle_is_cut_at_the_callers_annotations():
    from perf.reducers import trace_idle_by_caller as by_caller

    annotations = [(2.0, 4.0, "wait_device"), (4.0, 5.0, "other"), (5.0, 5.5, "transfer"),
                   (6.0, 8.0, "wait_device"), (8.0, 9.0, "tpusnap")]
    gaps = [(0.5, 1.5),   # before the hand-back: outside the annotations' extent
            (1.5, 2.5),   # half outside, half under wait_device
            (3.5, 5.25),  # wait_device 0.5, other 1.0, transfer 0.25
            (5.5, 6.5),   # 0.5 under no annotation, 0.5 under wait_device
            (8.5, 9.5)]   # tpusnap 0.5, and 0.5 after the take's end
    table = by_caller.attribute(gaps, annotations)
    assert table == pytest.approx({
        by_caller.OUTSIDE: 2.0, "wait_device": 1.5, "other": 1.0, "transfer": 0.25,
        by_caller.NONE: 0.5, "tpusnap": 0.5})
    waiting, elsewhere = by_caller.split(table)
    assert (waiting, elsewhere) == pytest.approx((1.5, 2.25))
    # Inside the extent the two parts are the idle seconds, all of them.
    assert waiting + elsewhere == pytest.approx(sum(table.values()) - table[by_caller.OUTSIDE])
    assert by_caller.attribute(gaps, []) == {}
    assert by_caller.attribute([], annotations) == {}


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


def test_the_reducer_reads_the_trace_beside_the_telemetry_dir(tmp_path, monkeypatch, capsys):
    import jax.profiler

    from perf.reducers import trace_idle_by_caller as by_caller

    run_dir = tmp_path / "trace" / "plugins" / "profile" / "2026_01_01"
    run_dir.mkdir(parents=True)
    (run_dir / "host.xplane.pb").write_bytes(b"")
    monkeypatch.setenv("TPUSNAP_TELEMETRY_DIR", str(tmp_path / "telemetry"))
    monkeypatch.setattr(by_caller, "_tables", {})
    planes = [
        ("/device:TPU:1", [("XLA Ops", [("fusion", 0.0, 100.0)])]),  # not device 0
        ("/device:TPU:0", [("XLA Ops", [("fusion.1", 0.0, 1.0), ("fusion.2", 3.0, 4.0),
                                        ("fusion.3", 6.0, 7.0), ("fusion.4", 7.0005, 8.0)])]),
        ("/host:CPU", [("python3", [("perf_anchor", 0.0, 0.001), ("tpusnap:prepare", 1.0, 2.0)]),
                       ("python3", [("tpusnap-caller:other", 2.0, 3.5),
                                    ("tpusnap-caller:wait_device", 3.5, 5.0),
                                    ("tpusnap-caller:transfer", 5.0, 5.5),
                                    ("tpusnap:stage.work", 2.0, 6.0)])]),
    ]
    monkeypatch.setattr(jax.profiler.ProfileData, "from_file",
                        staticmethod(lambda path: _fake_profile(planes)))
    obs = {"trace": {"window_s": 8.0, "busy_s": 4.0}}
    # Gaps 1-3 and 4-6 (0.5 ms is none). The annotations' extent is 2-5.5:
    # 1 s of the first gap and 0.5 s of the second lie outside it; inside,
    # other 1.0, wait_device 1.0, transfer 0.5.
    assert by_caller.reduce(obs, part="waiting") == pytest.approx(1000.0)
    assert by_caller.reduce(obs, part="elsewhere") == pytest.approx(1500.0)
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("perf idle_by_caller: ")]
    assert len(lines) == 1  # the table is printed once for the two metrics
    table = json.loads(lines[0].split(": ", 1)[1])
    assert table["idle_s"] == pytest.approx(4.0) and table["annotated_s"] == pytest.approx(3.5)
    assert dict(table["by_class"]) == pytest.approx(
        {"(outside)": 1.5, "other": 1.0, "wait_device": 1.0, "transfer": 0.5})
    assert _read("idle_caller_waiting_ms", obs) == pytest.approx(1000.0)
    assert _read("idle_caller_elsewhere_ms", obs) == pytest.approx(1500.0)
    # A program from before the watch (the parent of this change laid under
    # these files), a run with no device plane, no trace: nothing to read.
    silent = [(p, [(ln, [e for e in evs if not e[0].startswith("tpusnap-caller:")])
                   for ln, evs in lines_]) for p, lines_ in planes]
    monkeypatch.setattr(by_caller, "_tables", {})
    monkeypatch.setattr(jax.profiler.ProfileData, "from_file",
                        staticmethod(lambda path: _fake_profile(silent)))
    assert by_caller.reduce(obs, part="waiting") is None
    assert by_caller.reduce(obs, part="elsewhere") is None
    assert by_caller.reduce({"trace": None}, part="waiting") is None
    monkeypatch.setenv("TPUSNAP_TELEMETRY_DIR", str(tmp_path / "elsewhere" / "telemetry"))
    assert by_caller.reduce(obs, part="waiting") is None
    # The reading that was there keeps reading what it read: the caller's
    # annotations are not under its prefix.
    from perf.reducers import trace_unexplained_idle as tui

    monkeypatch.setenv("TPUSNAP_TELEMETRY_DIR", str(tmp_path / "telemetry"))
    monkeypatch.setattr(jax.profiler.ProfileData, "from_file",
                        staticmethod(lambda path: _fake_profile(planes)))
    _, annotations, _ = tui.read_planes(str(run_dir / "host.xplane.pb"))
    assert sorted(a[3] for a in annotations) == ["prepare", "stage.work"]


# ---- the manifest (no `manifest_shape` marker: `test_manifest_grows.py` pins the files that carry it)


@pytest.mark.parametrize("metric", NEW, ids=lambda m: m["name"])
def test_each_new_entry_lists_the_cells_that_report_what_it_moves(metric):
    from perf import harness

    assert set(metric) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert metric["better"] == "lower" and metric["workloads"]
    layers = {m["layer"] for m in MANIFEST["per_layer"] if m not in NEW}
    assert metric["layer"] in layers  # a layer the benchmark already names, letter for letter
    moved = next(m for m in MANIFEST["end_to_end"] if m["name"] == metric["moves"])
    for cell in metric["workloads"]:
        assert cell in moved.get("workloads", CELLS)
        assert (CELLS[cell]["chips"] == 4) == metric["name"].endswith(".sharded")
    spec = harness.layer_metric_spec(metric["name"])
    assert metric["source"] == {"span_per_op": "program_span", "span_union_per_op": "program_span",
                                "sampled_class_per_op": "program_span",
                                "counter_per_op": "program_counter",
                                "trace_idle_by_caller": "device_trace"}[spec["reducer"]]
    assert metric["unit"] == ("us" if spec["reducer"] == "counter_per_op" else "ms")
    if metric["moves"] == "resume_s":
        assert not metric["name"].endswith((".save", ".sharded"))
    # The caller's track belongs to the one-chip cells (PERF.md 7: the
    # four-chip cell's traced run holds its loop at the profiler's stop).
    if metric["name"].split(".")[0] in ("caller_other_ms", "caller_wait_device_ms",
                                        "caller_transfer_ms", "caller_runq_us", "process_cpu_us",
                                        "idle_caller_waiting_ms", "idle_caller_elsewhere_ms"):
        assert all(CELLS[c]["chips"] == 1 for c in metric["workloads"])


def test_the_new_entries_are_the_table_of_the_issue():
    assert sorted({m["name"].split(".")[0] for m in NEW}) == sorted(READINGS)
    assert len(NEW) >= 23
    # Appended: every entry that was there comes before the first new one.
    names = [m["name"] for m in MANIFEST["per_layer"]]
    first = min(names.index(m["name"]) for m in NEW)
    assert all(n.split(".")[0] not in READINGS for n in names[:first])
    for reading in READINGS:
        assert os.path.isfile(os.path.join(PERF, "layer_metrics", f"{reading}.json"))


# ---- the rehearsal of each cell


@pytest.mark.parametrize("cell", list(CELLS))
def test_the_rehearsal_of_each_cell_still_prints_its_line(tmp_path, cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={CELLS[cell]['chips']}",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    proc = subprocess.run(
        [sys.executable, os.path.join(PERF, "run.py"), "--workload", cell, "--seed",
         "4200000007", "--seconds", "2", "--trace", "1", "--rehearsal"],
        capture_output=True, text=True, timeout=400, cwd=ROOT, env=env)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] and result["failed"] == 0, proc.stdout[-3000:]
    mine = {m["name"] for m in NEW if cell in m["workloads"]}
    printed = {k: v["value"] for k, v in result["metrics"].items() if k in mine}
    # Times, so a rehearsal prints each as null. A reading whose span did not
    # fire is left out: the two that read the device's trace on the CPU, a
    # class the caller was never sampled in, the reads of blobs too small for
    # the native path.
    assert set(printed.values()) == {None}
    wanted = {n for n in mine if n.startswith((
        "write_resume", "stage_resume", "consume_resume", "consume_queue", "loop_late_window",
        "loop_runq", "watch_late", "process_cpu"))}
    assert wanted and wanted <= set(printed), (wanted, printed)
