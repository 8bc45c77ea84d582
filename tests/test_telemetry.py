"""Telemetry subsystem tests: span/counter recording, knob gating,
persisted Chrome traces, the cross-rank rollup, the ``trace`` CLI,
chaos-layer integration (injected faults + retries visible in the
trace), the RSS sampler, and the tier-1 overhead guard.
"""

import json
import logging
import os
import threading
import time

import numpy as np
import pytest

from tpusnap import (
    FaultPlan,
    MetricsSink,
    PytreeState,
    Snapshot,
    metrics_sink,
)
from tpusnap import telemetry
from tpusnap.knobs import is_telemetry_enabled, override_telemetry_enabled
from tpusnap.telemetry import (
    TakeTelemetry,
    rollup_summaries,
    telemetry_rank_path,
)


def _state(total_bytes=1 << 20, n=2):
    per = max(total_bytes // n // 4, 16)
    return {f"w{i}": np.arange(per, dtype=np.float32) + i for i in range(n)}


def _trace_file(snap_path, rank=0):
    return os.path.join(snap_path, ".tpusnap", "telemetry", f"rank_{rank}.json")


# ------------------------------------------------------------------ knob


def test_telemetry_knob_default_on():
    assert is_telemetry_enabled()


def test_telemetry_knob_env_and_override(monkeypatch):
    monkeypatch.setenv("TPUSNAP_TELEMETRY", "0")
    assert not is_telemetry_enabled()
    monkeypatch.setenv("TPUSNAP_TELEMETRY", "1")
    assert is_telemetry_enabled()
    with override_telemetry_enabled(False):
        assert not is_telemetry_enabled()
        with override_telemetry_enabled(True):
            assert is_telemetry_enabled()
        assert not is_telemetry_enabled()
    assert is_telemetry_enabled()


# ------------------------------------------------------- unit: recorder


def test_span_recording_and_summary_aggregates():
    rec = TakeTelemetry(rank=3, enabled=True)
    rec.record_span("x", 0.0, 0.2)
    rec.record_span("x", 0.2, 0.4)
    rec.record_span("x", 0.6, 0.6)
    rec.record_span("p", 0.0, 1.0, phase=True)
    rec.incr("c", 2)
    rec.incr("c")
    rec.gauge_max("g", 5.0)
    rec.gauge_max("g", 3.0)
    rec.finalize()
    s = rec.summary()
    assert s["rank"] == 3
    assert s["stages"]["x"]["count"] == 3
    assert s["stages"]["x"]["max_s"] == pytest.approx(0.6)
    assert s["stages"]["x"]["p50_s"] == pytest.approx(0.4)
    assert s["stages"]["x"]["total_s"] == pytest.approx(1.2)
    assert s["counters"]["c"] == 3
    assert s["gauges"]["g"] == 5.0
    assert s["phases"] == {"p": 1.0}


def test_spans_disabled_counters_still_on():
    rec = TakeTelemetry(rank=0, enabled=False)
    with rec.span("never"):
        pass
    rec.record_span("never", 0.0, 1.0)
    rec.event("never")
    rec.incr("still_counted")
    rec.finalize()
    s = rec.summary()
    assert s["stages"] == {}
    assert s["counters"] == {"still_counted": 1}
    assert not s["enabled"]


def test_counters_atomic_across_threads():
    rec = TakeTelemetry(rank=0, enabled=True)
    n_threads, n_incr = 8, 500

    def bump():
        for _ in range(n_incr):
            rec.incr("hits")

    threads = [threading.Thread(target=bump) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    rec.finalize()
    assert rec.summary()["counters"]["hits"] == n_threads * n_incr


def test_module_incr_updates_global_and_current():
    telemetry.reset_global_counters()
    rec = telemetry.begin_take(rank=0)
    try:
        telemetry.incr("test.counter", 2)
        assert telemetry.counter_value("test.counter") == 2
        assert rec.summary()["counters"]["test.counter"] == 2
    finally:
        telemetry.end_take(rec)
    # No take in flight: global still counts (always-on).
    telemetry.incr("test.counter")
    assert telemetry.counter_value("test.counter") == 3


def test_chrome_trace_events_shape():
    rec = TakeTelemetry(rank=1, enabled=True)
    with rec.span("work", phase=True, bytes=10):
        pass
    rec.event("boom", kind="write")
    rec.finalize()
    events = rec.chrome_trace_events()
    complete = [e for e in events if e.get("ph") == "X"]
    instants = [e for e in events if e.get("ph") == "i"]
    assert len(complete) == 1 and len(instants) == 1
    ev = complete[0]
    assert ev["name"] == "work" and ev["pid"] == 1
    assert isinstance(ev["ts"], float) and isinstance(ev["dur"], float)
    assert ev["args"]["bytes"] == 10
    assert ev["args"]["kind"] == "phase" and ev["args"]["op"] == rec.op
    assert ev["args"]["parent"] is None and ev["args"]["id"] > 0
    # Serializes as valid JSON end to end.
    doc = json.loads(rec.to_json())
    assert isinstance(doc["traceEvents"], list)


def test_rollup_summaries():
    a = {
        "take_wall_s": 1.0,
        "phase_coverage": 0.95,
        "stages": {"stage": {"count": 1, "total_s": 0.6, "p50_s": 0.6, "max_s": 0.6}},
        "counters": {"retry.attempts": 2, "storage.bytes_written": 100},
        "gauges": {"scheduler.budget_used_bytes": 50.0},
    }
    b = {
        "take_wall_s": 2.0,
        "phase_coverage": 0.91,
        "stages": {"stage": {"count": 1, "total_s": 0.8, "p50_s": 0.8, "max_s": 0.8}},
        "counters": {"retry.attempts": 1, "storage.bytes_written": 200},
        "gauges": {"scheduler.budget_used_bytes": 80.0},
    }
    r = rollup_summaries([a, b])
    assert r["ranks"] == 2
    assert r["take_wall_s"] == 2.0
    assert r["phase_coverage_min"] == 0.91
    assert r["stages"]["stage"]["max_s"] == pytest.approx(0.8)
    assert r["counters"]["retry.attempts"] == 3
    assert r["retry_attempts"] == 3
    assert r["bytes_written"] == 300
    assert r["budget_high_water_bytes"] == 80.0
    assert rollup_summaries([]) == {}


def test_metrics_sink_callbacks(tmp_path):
    seen = {"spans": [], "counters": [], "summaries": []}

    class Sink(MetricsSink):
        def on_span(self, name, duration_s, attrs):
            seen["spans"].append(name)

        def on_counter(self, name, delta, value):
            seen["counters"].append(name)

        def on_take_summary(self, summary):
            seen["summaries"].append(summary)

    with metrics_sink(Sink()):
        Snapshot.take(str(tmp_path / "snap"), {"m": PytreeState(_state())})
    assert "stage" in seen["spans"]
    assert "storage.writes" in seen["counters"]
    assert len(seen["summaries"]) == 1
    assert seen["summaries"][0]["phase_coverage"] > 0.5
    # Unregistered: no further callbacks.
    n = len(seen["counters"])
    telemetry.incr("post.unregister")
    assert len(seen["counters"]) == n


def test_raising_sink_never_breaks_a_take(tmp_path):
    class BadSink(MetricsSink):
        def on_span(self, name, duration_s, attrs):
            raise RuntimeError("bad sink")

        def on_counter(self, name, delta, value):
            raise RuntimeError("bad sink")

        def on_take_summary(self, summary):
            raise RuntimeError("bad sink")

    with metrics_sink(BadSink()):
        snap = Snapshot.take(str(tmp_path / "snap"), {"m": PytreeState(_state())})
    assert snap.verify().clean


def test_raising_sink_warns_once_per_callback_per_take(tmp_path, caplog):
    """A broken exporter must be diagnosable, not invisible: one
    rate-limited WARNING per sink class per callback per take, naming
    both — and the budget re-arms on the next take."""

    class BoomSink(MetricsSink):
        def on_span(self, name, duration_s, attrs):
            raise RuntimeError("boom")

        def on_counter(self, name, delta, value):
            raise RuntimeError("boom")

    def warnings_for(records, method):
        return [
            r
            for r in records
            if r.levelname == "WARNING"
            and "BoomSink" in r.message
            and method in r.message
        ]

    with metrics_sink(BoomSink()):
        with caplog.at_level(logging.WARNING, logger="tpusnap.telemetry"):
            Snapshot.take(str(tmp_path / "s1"), {"m": PytreeState(_state())})
        # Many spans and counters fired; exactly ONE warning per callback.
        assert len(warnings_for(caplog.records, "on_span")) == 1
        assert len(warnings_for(caplog.records, "on_counter")) == 1
        caplog.clear()
        with caplog.at_level(logging.WARNING, logger="tpusnap.telemetry"):
            Snapshot.take(str(tmp_path / "s2"), {"m": PytreeState(_state())})
        # Fresh take -> the one-warning budget re-arms.
        assert len(warnings_for(caplog.records, "on_span")) == 1


def test_metrics_sink_context_manager_unregisters_on_raise():
    """A failing test body can no longer leak its sink into the
    process-global tuple (the leak the context manager exists to fix)."""
    calls = []

    class Sink(MetricsSink):
        def on_counter(self, name, delta, value):
            calls.append(name)

    sink = Sink()
    with pytest.raises(RuntimeError):
        with metrics_sink(sink) as registered:
            assert registered is sink
            telemetry.incr("ctx.mgr.counter")
            raise RuntimeError("body failed")
    n = len(calls)
    assert n >= 1
    telemetry.incr("ctx.mgr.counter")  # after exit: no callback
    assert len(calls) == n


# ------------------------------------------------- persisted trace files


def test_take_persists_trace_and_rollup(tmp_path):
    path = str(tmp_path / "snap")
    snap = Snapshot.take(path, {"m": PytreeState(_state())})
    tf = _trace_file(path)
    assert os.path.exists(tf)
    doc = json.load(open(tf))
    assert doc["rank"] == 0
    events = doc["traceEvents"]
    assert isinstance(events, list) and events
    for ev in events:
        assert "ph" in ev and "pid" in ev
        if ev["ph"] in ("X", "i"):
            assert "ts" in ev and "name" in ev
    s = doc["summary"]
    # Acceptance: per-stage phases cover >= 90% of the take wall-clock.
    assert s["phase_coverage"] >= 0.9
    for phase in ("state_dict", "prepare", "stage", "io_drain"):
        assert phase in s["phases"], phase
    assert s["counters"]["storage.bytes_written"] > 0
    assert "peak_rss_delta_bytes" in s["gauges"]
    assert "scheduler.budget_used_bytes" in s["gauges"]
    # Rank-0 rollup rides the committed metadata extras.
    rollup = snap.metadata.extras["telemetry"]
    assert rollup["ranks"] == 1
    assert rollup["bytes_written"] == s["counters"]["storage.bytes_written"]
    # The trace sidecar files do not perturb integrity machinery.
    assert snap.verify().clean


def test_async_take_persists_trace(tmp_path):
    path = str(tmp_path / "snap")
    pending = Snapshot.async_take(path, {"m": PytreeState(_state())})
    snap = pending.wait()
    doc = json.load(open(_trace_file(path)))
    assert doc["summary"]["phase_coverage"] >= 0.85
    assert "io_drain" in doc["summary"]["phases"]
    assert "telemetry" in snap.metadata.extras


def test_telemetry_disabled_skips_trace_file(tmp_path):
    path = str(tmp_path / "snap")
    with override_telemetry_enabled(False):
        snap = Snapshot.take(path, {"m": PytreeState(_state())})
    assert not os.path.exists(_trace_file(path))
    # Counters are always-on: the rollup still lands in the extras.
    rollup = (snap.metadata.extras or {}).get("telemetry")
    assert rollup is not None
    assert rollup["bytes_written"] > 0
    assert rollup["stages"] == {}


def test_last_take_summary_exposed(tmp_path):
    Snapshot.take(str(tmp_path / "snap"), {"m": PytreeState(_state())})
    s = telemetry.LAST_TAKE_SUMMARY
    assert s is not None and s["counters"]["storage.writes"] >= 1


def test_clean_take_records_no_fatal_payload_retries(tmp_path):
    """Regression (a stray ``retry.fatal.read: 1``): the journal
    probe at take start 404s on every fresh path, and other
    sidecar-namespace misses are expected probes, not payload failures —
    none of them may surface as ``retry.fatal.*`` payload counters in
    the take's stage_breakdown. The sidecar family keeps its own label
    (``retry.fatal.sidecar.*``) so real sidecar storage failures stay
    observable."""
    Snapshot.take(str(tmp_path / "snap"), {"m": PytreeState(_state())})
    counters = telemetry.LAST_TAKE_SUMMARY["counters"]
    fatal_payload = {
        k: v
        for k, v in counters.items()
        if k.startswith("retry.fatal.")
        and not k.startswith("retry.fatal.sidecar.")
    }
    assert not fatal_payload, fatal_payload
    # The probe that used to pollute the payload counter is the journal
    # read; on a fresh path it lands under the sidecar family instead.
    assert counters.get("retry.fatal.sidecar.read", 0) >= 1, counters


# ------------------------------------------------------------ trace CLI


def test_trace_cli_renders_and_json(tmp_path, capsys):
    from tpusnap.__main__ import main

    path = str(tmp_path / "snap")
    Snapshot.take(path, {"m": PytreeState(_state())})
    assert main(["trace", path]) == 0
    out = capsys.readouterr().out
    assert "stage" in out and "phase coverage" in out
    assert main(["trace", path, "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["rollup"]["ranks"] == 1
    assert "0" in doc["ranks"]
    assert main(["trace", path, "--rank", "0"]) == 0
    assert "rank 0 stages" in capsys.readouterr().out


def test_trace_cli_no_telemetry_exits_3(tmp_path, capsys):
    from tpusnap.__main__ import main

    path = str(tmp_path / "snap")
    with override_telemetry_enabled(False):
        snap = Snapshot.take(path, {"m": PytreeState(_state())})
    # Strip the always-on rollup too: simulate a pre-telemetry snapshot.
    meta = json.load(open(os.path.join(path, ".snapshot_metadata")))
    meta.pop("extras", None)
    # Rewriting the file invalidates its self-checksum; per the format
    # spec a rewriter strips (or recomputes) the field.
    meta.pop("self_checksum", None)
    with open(os.path.join(path, ".snapshot_metadata"), "w") as f:
        json.dump(meta, f)
    del snap
    assert main(["trace", path]) == 3
    assert "no telemetry" in capsys.readouterr().err


def test_trace_cli_knob_off_take_exits_3(tmp_path, capsys):
    """The OTHER no-telemetry case: a knob-off take still rolls up its
    always-on counters into the extras, but has zero spans anywhere —
    trace must print the one-line explanation and exit 3 instead of an
    empty stage table."""
    from tpusnap.__main__ import main

    path = str(tmp_path / "snap")
    with override_telemetry_enabled(False):
        Snapshot.take(path, {"m": PytreeState(_state())})
    assert main(["trace", path]) == 3
    captured = capsys.readouterr()
    assert "no telemetry" in captured.err
    assert "stage" not in captured.out  # no empty table printed


def test_cli_help_lists_trace(capsys):
    from tpusnap.__main__ import main

    assert main(["--help"]) == 0
    assert "trace" in capsys.readouterr().out


# ----------------------------------------------------- chaos integration


@pytest.mark.chaos
def test_chaos_trace_records_faults_and_retries(tmp_path, caplog):
    path = str(tmp_path / "chaos_snap")
    with caplog.at_level(logging.INFO, logger="tpusnap.retry"):
        Snapshot.take(
            "chaos+fs://" + path,
            {"m": PytreeState(_state())},
            storage_options={"fault_plan": FaultPlan(seed=3, transient_per_op=1)},
        )
    doc = json.load(open(_trace_file(path)))
    counters = doc["summary"]["counters"]
    assert counters.get("faults.injected.write", 0) >= 1
    assert counters.get("retry.attempts", 0) >= 1
    assert counters.get("retry.recovered", 0) >= 1
    assert any(
        k.startswith("retry.transient.write.InjectedFaultError")
        for k in counters
    )
    # The injected faults + retries appear as instant events in the trace.
    instants = {e["name"] for e in doc["traceEvents"] if e.get("ph") == "i"}
    assert "fault_injected" in instants and "retry" in instants
    # Success-after-retry now logs the attempt count at INFO.
    assert any("succeeded after" in r.message for r in caplog.records)
    # And the committed rollup carries the fault/retry counters.
    md = json.load(open(os.path.join(path, ".snapshot_metadata")))
    assert md["extras"]["telemetry"]["retry_attempts"] >= 1


# ---------------------------------------------------------- RSS sampler


def test_rss_sampler_start_stop_clean():
    from tpusnap.rss_profiler import RSSSampler

    sampler = RSSSampler(interval_sec=0.02)
    sampler.start()
    time.sleep(0.08)
    deltas = sampler.stop()
    assert deltas, "sampler recorded nothing"
    assert all(isinstance(d, int) for d in deltas)
    # Idempotent stop, thread actually gone.
    n = len(deltas)
    assert sampler.stop() is deltas and len(deltas) == n
    assert not any(t.name == "tpusnap-rss" for t in threading.enumerate())


def test_rss_sampler_records_final_delta_for_sub_interval_context():
    from tpusnap.rss_profiler import RSSSampler

    sampler = RSSSampler(interval_sec=10.0)
    sampler.start()
    deltas = sampler.stop()  # stop long before the first interval tick
    assert len(deltas) == 1  # the final sample


def test_measure_rss_deltas_context_manager():
    from tpusnap.rss_profiler import measure_rss_deltas

    deltas = []
    with measure_rss_deltas(deltas, interval_sec=0.01):
        blob = np.ones(4 << 20, dtype=np.uint8)  # ~4MB so RSS moves
        time.sleep(0.05)
        del blob
    assert deltas
    assert deltas[-1] is not None  # final delta appended on exit


def test_take_summary_includes_peak_rss(tmp_path):
    Snapshot.take(str(tmp_path / "snap"), {"m": PytreeState(_state())})
    assert "peak_rss_delta_bytes" in telemetry.LAST_TAKE_SUMMARY["gauges"]


# -------------------------------------------------------- overhead guard


def test_telemetry_overhead_within_bound(tmp_path):
    """Tier-1 guard: a small take with telemetry enabled stays within
    10% (+50ms absolute timing slack) of disabled — catches accidental
    hot-path regressions (per-element spans, lock convoys) without
    flaking on scheduler noise. min-of-N so one slow run cannot fail it."""
    state = _state(total_bytes=16 << 20, n=8)

    def take_once(i, enabled):
        with override_telemetry_enabled(enabled):
            t0 = time.perf_counter()
            Snapshot.take(
                str(tmp_path / f"s_{enabled}_{i}"), {"m": PytreeState(state)}
            )
            return time.perf_counter() - t0

    take_once(99, True)  # warmup: imports, native lib load
    runs = 5
    disabled = min(take_once(i, False) for i in range(runs))
    enabled = min(take_once(i, True) for i in range(runs))
    assert enabled <= disabled * 1.10 + 0.05, (
        f"telemetry overhead too high: enabled {enabled:.3f}s vs "
        f"disabled {disabled:.3f}s"
    )


# ------------------------------------------------------------ distributed


def _world_telemetry_take(snap_dir):
    import jax.numpy as jnp

    from tpusnap import Snapshot, StateDict
    from tpusnap.comm import get_communicator

    comm = get_communicator()
    state = StateDict(
        w=jnp.arange(4096, dtype=jnp.float32) * (comm.rank + 1),
        b=jnp.ones(64, jnp.float32),
    )
    Snapshot.take(snap_dir, {"model": state})
    comm.barrier()
    if comm.rank == 0:
        for r in range(comm.world_size):
            p = os.path.join(snap_dir, ".tpusnap", "telemetry", f"rank_{r}.json")
            assert os.path.exists(p), f"missing trace for rank {r}"
            doc = json.load(open(p))
            assert doc["traceEvents"], f"rank {r} trace empty"
            assert doc["summary"]["phase_coverage"] >= 0.9, doc["summary"]
        md = json.load(open(os.path.join(snap_dir, ".snapshot_metadata")))
        rollup = md["extras"]["telemetry"]
        assert rollup["ranks"] == comm.world_size
        # Collective waits are visible per rank.
        assert "comm.all_gather" in rollup["stages"]
        assert rollup["bytes_written"] > 0


@pytest.mark.distributed
def test_distributed_take_produces_rank_traces(tmp_path):
    from tpusnap.test_utils import run_subprocess_world

    run_subprocess_world(
        _world_telemetry_take, world_size=2, args=[str(tmp_path / "snap")]
    )
