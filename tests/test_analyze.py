"""Performance attribution: log2 histograms, critical-path bound
analysis, in-take roofline probes, and the `tpusnap analyze` doctor CLI.

The math tests run on synthetic spans/values with zero sleeps (the
attribution sweep and the histograms are pure functions of recorded
data); the CLI tests drive real takes through `python -m tpusnap
analyze`, including the zero-span/pre-telemetry exit-3 contract that
matches `trace`; the 2-proc test asserts the cross-rank histogram merge
in the metadata rollup.
"""

import glob
import json
import os

import numpy as np
import pytest

from tpusnap import PytreeState, Snapshot, telemetry
from tpusnap.__main__ import main
from tpusnap.analyze import (
    Thresholds,
    analyze,
    attribute_spans,
    classify_span,
    straggler_findings,
    tail_latency_findings,
)
from tpusnap.knobs import (
    override_probe,
    override_telemetry_dir,
    override_telemetry_enabled,
)
from tpusnap.progress import load_restore_traces
from tpusnap.telemetry import IOStats, LogHistogram


def _state(total_bytes=8 << 20, n=4):
    per = total_bytes // n
    return {
        f"w{i}": np.random.default_rng(i).integers(
            0, 255, per, dtype=np.uint8
        )
        for i in range(n)
    }


# ------------------------------------------------------- LogHistogram


def test_log_histogram_bucketing():
    h = LogHistogram()
    for v in (1.0, 1.5, 2.0, 3.99, 4.0, 0.0):
        h.observe(v)
    # [1,2): 1.0, 1.5 -> bucket 0; [2,4): 2.0, 3.99 -> bucket 1;
    # [4,8): 4.0 -> bucket 2; zero -> the zero bucket.
    assert h.buckets[0] == 2
    assert h.buckets[1] == 2
    assert h.buckets[2] == 1
    assert h.count == 6
    assert h.vmax == 4.0
    assert h.vmin == 0.0
    assert abs(h.total - 12.49) < 1e-9


def test_log_histogram_quantiles_exact_at_extremes():
    h = LogHistogram()
    assert h.quantile(0.5) is None  # empty
    h.observe(0.004)
    # Single sample: every quantile is that sample (clamped to max).
    assert h.quantile(0.5) == pytest.approx(0.004)
    assert h.quantile(1.0) == pytest.approx(0.004)
    for _ in range(99):
        h.observe(0.001)
    h.observe(10.0)
    # p50 lives in the 0.001 bucket; max is exact.
    assert h.quantile(0.5) <= 0.002048
    assert h.quantile(1.0) == pytest.approx(10.0)
    # The fat tail is visible: p99 >> p50 once the outlier has weight.
    for _ in range(10):
        h.observe(10.0)
    assert h.quantile(0.99) == pytest.approx(10.0)


def test_log_histogram_merge_preserves_tails():
    a, b = LogHistogram(), LogHistogram()
    for _ in range(100):
        a.observe(0.001)
    b.observe(5.0)  # one rank's outlier
    a.merge(b)
    assert a.count == 101
    assert a.quantile(1.0) == pytest.approx(5.0)
    # Round-trips through the serialized form (the rollup transport).
    c = LogHistogram.from_dict(a.to_dict())
    assert c.count == a.count
    assert c.quantile(1.0) == pytest.approx(5.0)
    assert c.buckets == a.buckets


def test_iostats_quantile_fields_and_merge():
    st = IOStats()
    for _ in range(98):
        st.observe(0.002, 1 << 20)
    st.observe(0.5, 1 << 20)  # tail writes (2% mass so p99 sees them)
    st.observe(0.5, 1 << 20)
    d = st.to_dict()
    assert d["count"] == 100
    assert d["bytes_total"] == 100 << 20
    assert d["p50_s"] <= 0.004096
    assert d["max_s"] == pytest.approx(0.5)
    assert d["p99_s"] >= 0.25  # the tail bucket
    other = IOStats()
    other.merge_dict(d)
    other.merge_dict(d)
    assert other.to_dict()["count"] == 200


def test_merge_io_histograms_across_ranks():
    r0, r1 = IOStats(), IOStats()
    for _ in range(10):
        r0.observe(0.001, 1 << 20)
    r1.observe(2.0, 1 << 20)  # rank 1's straggler write
    merged = telemetry.merge_io_histograms(
        [
            {"write.FSStoragePlugin": r0.to_dict()},
            {"write.FSStoragePlugin": r1.to_dict()},
        ]
    )
    st = merged["write.FSStoragePlugin"]
    assert st["count"] == 11
    assert st["max_s"] == pytest.approx(2.0)


# -------------------------------------------------------- attribution


def test_classify_span_taxonomy():
    assert classify_span("storage_write") == "storage_write"
    assert classify_span("stage_buffer") == "stage"
    assert classify_span("dtoh") == "dtoh"
    assert classify_span("checksum_late") == "checksum"
    assert classify_span("cow_verify") == "checksum"
    assert classify_span("comm.barrier") == "barrier"
    assert classify_span("kv.barrier_arrive") == "barrier"
    assert classify_span("budget_wait") == "budget_wait"
    # Containers and unknown names never attribute.
    assert classify_span("stage_window") is None
    assert classify_span("probe_roofline") is None
    assert classify_span("some_future_span") is None


def test_attribution_single_category_full_coverage():
    att = attribute_spans([("storage_write", 0.0, 10.0)], wall_s=10.0)
    assert att.attributed == {"storage_write": pytest.approx(10.0)}
    assert att.unattributed_s == pytest.approx(0.0)
    assert att.verdict() == ("storage_write", pytest.approx(1.0))


def test_attribution_io_wins_overlap_and_glue_is_unattributed():
    # stage [0,4], write [2,8], wall 10: write owns [2,8] (I/O-first
    # tiebreak), stage only its solo [0,2], [8,10] is glue.
    att = attribute_spans(
        [("stage_buffer", 0.0, 4.0), ("storage_write", 2.0, 6.0)],
        wall_s=10.0,
    )
    assert att.attributed["storage_write"] == pytest.approx(6.0)
    assert att.attributed["stage"] == pytest.approx(2.0)
    assert att.unattributed_s == pytest.approx(2.0)
    # Raw busy time ignores the overlap exclusivity.
    assert att.busy["stage"] == pytest.approx(4.0)
    assert att.coverage == pytest.approx(0.8)


def test_attribution_waits_only_when_idle():
    # budget_wait under in-flight I/O is storage-bound (writes are the
    # only budget source); a bare budget_wait is budget-bound.
    att = attribute_spans(
        [
            ("budget_wait", 0.0, 5.0),
            ("storage_write", 0.0, 5.0),
            ("budget_wait", 5.0, 3.0),
        ],
        wall_s=8.0,
    )
    assert att.attributed["storage_write"] == pytest.approx(5.0)
    assert att.attributed["budget_wait"] == pytest.approx(3.0)
    assert att.unattributed_s == pytest.approx(0.0)


def test_attribution_barrier_lowest_priority_and_clipping():
    att = attribute_spans(
        [
            ("comm.barrier", 0.0, 4.0),
            ("checksum", 1.0, 2.0),
            ("storage_read", 6.0, 100.0),  # clipped to wall
            ("stage_window", 0.0, 10.0),  # container: ignored
        ],
        wall_s=10.0,
    )
    assert att.attributed["checksum"] == pytest.approx(2.0)
    assert att.attributed["barrier"] == pytest.approx(2.0)  # [0,1]+[3,4]
    assert att.attributed["storage_read"] == pytest.approx(4.0)
    assert att.unattributed_s == pytest.approx(2.0)  # [4,6]
    total = sum(att.attributed.values()) + att.unattributed_s
    assert total == pytest.approx(10.0)


def test_attribution_overlapping_same_category_not_double_counted():
    # 16 concurrent writes over the same 5 s attribute 5 s, not 80.
    spans = [("storage_write", 0.0, 5.0) for _ in range(16)]
    att = attribute_spans(spans, wall_s=5.0)
    assert att.attributed["storage_write"] == pytest.approx(5.0)
    assert att.busy["storage_write"] == pytest.approx(5.0)


def test_attribution_empty_spans():
    att = attribute_spans([], wall_s=3.0)
    assert att.attributed == {}
    assert att.unattributed_s == pytest.approx(3.0)
    assert att.verdict() is None


# ----------------------------------------------------------- findings


def test_tail_latency_finding_fires_on_fat_tail():
    st = IOStats()
    for _ in range(98):
        st.observe(0.002, 1 << 20)
    st.observe(0.9, 1 << 20)
    st.observe(0.9, 1 << 20)
    hist = {"write.FSStoragePlugin": st.to_dict()}
    out = tail_latency_findings(hist, Thresholds(p99_ratio=20.0))
    assert len(out) == 1
    assert out[0].severity == "warn"
    assert "write.FSStoragePlugin" in out[0].message
    # Below the ratio threshold: quiet.
    assert not tail_latency_findings(hist, Thresholds(p99_ratio=10_000.0))
    # Too few samples to call a tail: quiet.
    tiny = IOStats()
    tiny.observe(0.001, 1)
    tiny.observe(1.0, 1)
    assert not tail_latency_findings(
        {"write.X": tiny.to_dict()}, Thresholds(p99_ratio=2.0)
    )


def test_straggler_finding_from_rollup_skew():
    rollup = {
        "ranks": 4,
        "phase_skew": {
            "stage": {"p50_s": 1.0, "max_s": 3.5, "max_rank": 2, "skew": 3.5}
        },
    }
    out = straggler_findings(rollup, Thresholds(max_skew=2.0))
    assert len(out) == 1 and "rank 2" in out[0].message
    # Single-rank rollups have no stragglers by construction.
    assert not straggler_findings({**rollup, "ranks": 1}, Thresholds())


def test_analyze_report_shape_on_synthetic_docs():
    doc = {
        "summary": {
            "rank": 0,
            "take_wall_s": 10.0,
            "stages": {"storage_write": {"count": 1}},
        },
        "traceEvents": [
            {
                "name": "storage_write",
                "ph": "X",
                "cat": "op",
                "ts": 0.0,
                "dur": 9e6,
            },
            {"name": "stage", "ph": "X", "cat": "phase", "ts": 0, "dur": 1e7},
        ],
    }
    report = analyze({}, {0: doc}, kind="take")
    assert report["bound_by"] == "storage_write"
    assert report["bound_pct"] == pytest.approx(90.0)
    assert "TPUSNAP" in report["advice"]
    assert report["attribution"]["coverage"] == pytest.approx(0.9)
    assert report["check_failed"] is False


# ----------------------------------------------------- probe runner


def test_probe_records_samples_and_cleans_up(tmp_path):
    snap = str(tmp_path / "snap")
    with override_probe(True, interval_bytes=1 << 20, probe_bytes=1 << 20):
        Snapshot.take(snap, {"m": PytreeState(_state())})
    s = telemetry.LAST_TAKE_SUMMARY
    assert s["probe"]["probes"] >= 1
    assert s["probe"]["write_gbps_p50"] > 0
    assert s["probe"]["read_gbps_p50"] > 0
    assert 0 < s["roofline_fraction"]
    assert s["counters"]["probe.probes"] >= 1
    # Probe files are transient: none survive the take.
    assert not glob.glob(os.path.join(snap, ".tpusnap", "probe", "*"))
    # The probe rides the rollup too (single-rank fold).
    md = json.load(open(os.path.join(snap, ".snapshot_metadata")))
    rollup = md["extras"]["telemetry"]
    assert rollup["roofline_fraction"] == s["roofline_fraction"]
    assert rollup["probe"]["probes"] == s["probe"]["probes"]
    # And the history event carries the drift-immune fraction.
    from tpusnap.history import event_from_summary

    ev = event_from_summary("take", s)
    assert ev["roofline_fraction"] == s["roofline_fraction"]
    assert ev["probe_write_gbps"] == s["probe"]["write_gbps_p50"]


def test_probe_off_by_default(tmp_path):
    Snapshot.take(str(tmp_path / "snap"), {"m": PytreeState(_state())})
    s = telemetry.LAST_TAKE_SUMMARY
    assert "probe" not in s
    assert "roofline_fraction" not in s


def test_small_take_still_gets_one_probe(tmp_path):
    # Interval far above the take's bytes: the end-of-drain fallback
    # still measures once, so no probe-enabled take is fraction-less.
    with override_probe(True, interval_bytes=1 << 40, probe_bytes=1 << 20):
        Snapshot.take(str(tmp_path / "snap"), {"m": PytreeState(_state())})
    assert telemetry.LAST_TAKE_SUMMARY["probe"]["probes"] == 1


def test_probe_runner_stands_down_after_failure():
    """One failed probe disables probing for the take (one WARNING, no
    retry storm) — and the drain-end fallback respects the stand-down."""
    import asyncio

    from tpusnap.io_types import StoragePlugin
    from tpusnap.scheduler import _ProbeRunner

    class BoomPlugin(StoragePlugin):
        async def write(self, write_io):
            raise OSError("probe traffic rejected")

        async def read(self, read_io):
            raise OSError("nope")

        async def delete(self, path):
            pass

    with override_probe(True, interval_bytes=1 << 20, probe_bytes=1 << 20):
        tele = telemetry.TakeTelemetry(rank=0, enabled=True)
        try:
            runner = _ProbeRunner(BoomPlugin(), rank=0, tele=tele)
            runner.note_written(1 << 30)
            assert runner.due
            asyncio.run(runner.run())
        finally:
            # A bare TakeTelemetry (no end_take) starts an RSS sampler
            # thread; stop it or it outlives the test forever.
            tele.finalize()
    assert runner.ran == 0
    assert runner._failed
    runner.note_written(1 << 30)
    assert not runner.due  # stood down: never due again this take
    assert "probe" not in tele.summary()


def test_probe_excluded_from_async_blocked_window(tmp_path):
    """Probes never run inside a pipelined async take's blocked window
    — they would bill their I/O to async_blocked_s, the metric
    async_take exists to minimize. Every probe span starts after the
    blocked window closed."""
    snap = str(tmp_path / "snap")
    with override_probe(True, interval_bytes=1 << 20, probe_bytes=1 << 20):
        pending = Snapshot.async_take(
            snap, {"m": PytreeState(_state(total_bytes=16 << 20))}
        )
        pending.wait()
    s = telemetry.LAST_TAKE_SUMMARY
    assert s["probe"]["probes"] >= 1
    blocked_s = s["async_blocked_s"]
    doc = json.load(
        open(os.path.join(snap, ".tpusnap", "telemetry", "rank_0.json"))
    )
    probe_starts = [
        ev["ts"] / 1e6
        for ev in doc["traceEvents"]
        if ev.get("name") == "probe_roofline" and ev.get("ph") == "X"
    ]
    assert probe_starts, "no probe spans recorded"
    assert all(ts >= blocked_s for ts in probe_starts), (
        probe_starts,
        blocked_s,
    )


def test_stranded_probe_file_does_not_make_aborted_dir_foreign(tmp_path):
    """A probe stream a flaky backend's failed cleanup strands in an
    otherwise-cleaned (aborted) dir must not classify the path as
    'foreign' — gc refuses foreign, which would lock the checkpoint
    path against reuse. It reads as empty/reusable, like a leftover
    heartbeat record."""
    from tpusnap.lifecycle import fsck_snapshot

    d = tmp_path / "snap" / ".tpusnap" / "probe"
    d.mkdir(parents=True)
    (d / "rank_0_0.bin").write_bytes(b"x" * 1024)
    report = fsck_snapshot(str(tmp_path / "snap"))
    assert report.state == "empty", (report.state, report.detail)


# ------------------------------------------- probe runner: read path


def _probe_restore(tmp_path, total_bytes=64 << 20, n=8):
    """Take, then restore with in-restore probes on. Returns the
    restore summary and rank 0's persisted restore trace doc."""
    from tpusnap import compress

    snap = str(tmp_path / "snap")
    state = _state(total_bytes=total_bytes, n=n)
    Snapshot.take(snap, {"m": PytreeState(state)})
    compress._reset_ceilings()
    with override_telemetry_dir(str(tmp_path / "teledir")):
        with override_probe(
            True, interval_bytes=16 << 20, probe_bytes=1 << 20
        ):
            Snapshot(snap).restore(
                {
                    "m": PytreeState(
                        {k: np.zeros_like(v) for k, v in state.items()}
                    )
                }
            )
        docs = load_restore_traces(snap)
    return snap, telemetry.LAST_RESTORE_SUMMARY, docs[0]


def test_restore_probe_feeds_read_lane_and_history(tmp_path):
    """In-restore probes (TPUSNAP_PROBE=1): the restore summary gets
    the read-lane fraction, the ceiling registry gets a read-lane
    entry, no probe scratch survives, and the history event carries
    the drift-immune read fields."""
    from tpusnap import compress
    from tpusnap.history import event_from_summary

    snap, s, _doc = _probe_restore(tmp_path)
    assert s["probe"]["probes"] >= 1
    assert s["probe"]["read_gbps_p50"] > 0
    assert 0 < s["restore_roofline_fraction"]
    # The write-lane fraction belongs to takes — a restore summary
    # must not grow one.
    assert "roofline_fraction" not in s
    lanes = {lane for (_label, lane) in compress.pipe_ceilings_snapshot()}
    assert "read" in lanes
    assert not glob.glob(os.path.join(snap, ".tpusnap", "probe", "*"))
    ev = event_from_summary("restore", s)
    assert ev["restore_roofline_fraction"] == s["restore_roofline_fraction"]
    assert ev["probe_read_gbps"] == s["probe"]["read_gbps_p50"]
    assert "roofline_fraction" not in ev


def test_restore_probe_spans_outside_read_windows(tmp_path):
    """Probes only run while no blob read is in flight — a probe
    interleaved with reads would bill its own I/O to the storage_read
    window it exists to price. No probe span may overlap any
    storage_read span in the restore trace."""
    _snap, s, doc = _probe_restore(tmp_path)
    assert s["probe"]["probes"] >= 1
    spans = {"probe_roofline": [], "storage_read": []}
    for ev in doc["traceEvents"]:
        if ev.get("ph") == "X" and ev.get("name") in spans:
            spans[ev["name"]].append((ev["ts"], ev["ts"] + ev["dur"]))
    assert spans["probe_roofline"] and spans["storage_read"]
    for p0, p1 in spans["probe_roofline"]:
        for r0, r1 in spans["storage_read"]:
            assert p1 <= r0 or r1 <= p0, (
                "probe span overlaps a read window",
                (p0, p1),
                (r0, r1),
            )


def test_restore_probe_stands_down_on_read_lane():
    """The stand-down contract holds on the restore side too: one
    failed probe disables probing for the restore, and the summary
    grows neither a probe block nor restore_roofline_fraction."""
    import asyncio

    from tpusnap.io_types import StoragePlugin
    from tpusnap.scheduler import _ProbeRunner

    class BoomPlugin(StoragePlugin):
        async def write(self, write_io):
            raise OSError("probe scratch rejected")

        async def read(self, read_io):
            raise OSError("nope")

        async def delete(self, path):
            pass

    with override_probe(True, interval_bytes=1 << 20, probe_bytes=1 << 20):
        tele = telemetry.TakeTelemetry(rank=0, enabled=True)
        tele.meta["kind"] = "restore"
        try:
            runner = _ProbeRunner(BoomPlugin(), rank=0, tele=tele)
            runner.note_written(1 << 30)
            assert runner.due
            asyncio.run(runner.run())
        finally:
            tele.finalize()
    assert runner.ran == 0
    assert runner._failed
    runner.note_written(1 << 30)
    assert not runner.due  # stood down for the rest of this restore
    s = tele.summary()
    assert "probe" not in s
    assert "restore_roofline_fraction" not in s


def test_quantile_geometric_interpolation_stays_in_bucket():
    # The interpolated estimate never leaves the bucket that holds the
    # target rank, and clamps to the exact observed extremes.
    h = LogHistogram()
    for _ in range(50):
        h.observe(0.001)
    for _ in range(50):
        h.observe(0.003)
    p25, p75 = h.quantile(0.25), h.quantile(0.75)
    assert 0.0009765625 <= p25 <= 0.001953125  # 0.001's bucket
    assert 0.001953125 <= p75 <= 0.00390625  # 0.003's bucket
    assert p25 >= h.vmin and p75 <= h.vmax


# -------------------------------------------------- take histograms


def test_take_summary_records_io_histograms(tmp_path):
    Snapshot.take(str(tmp_path / "snap"), {"m": PytreeState(_state())})
    s = telemetry.LAST_TAKE_SUMMARY
    hist = s["io_histograms"]
    write = hist["write.FSStoragePlugin"]
    assert write["count"] > 0
    assert write["bytes_total"] >= 8 << 20
    assert write["p50_s"] is not None and write["p99_s"] >= write["p50_s"]
    # The rollup in metadata carries the merged copy. It is snapshotted
    # BEFORE the commit barrier, so the trace-sidecar and metadata
    # writes that follow are in the final summary but not in it.
    md = json.load(
        open(os.path.join(tmp_path, "snap", ".snapshot_metadata"))
    )
    merged = md["extras"]["telemetry"]["io_histograms"][
        "write.FSStoragePlugin"
    ]
    assert 0 < merged["count"] <= write["count"]
    assert merged["p99_s"] is not None


def test_histograms_recorded_even_with_telemetry_off(tmp_path):
    # Histograms are always-on like the counters (the knob gates spans).
    telemetry.reset_global_io_histograms()
    with override_telemetry_enabled(False):
        Snapshot.take(str(tmp_path / "snap"), {"m": PytreeState(_state())})
    g = telemetry.global_io_histograms_snapshot()
    assert g["write.FSStoragePlugin"]["count"] > 0


# ------------------------------------------------------ analyze CLI


def _probe_take(tmp_path):
    snap = str(tmp_path / "snap")
    with override_probe(True, interval_bytes=4 << 20, probe_bytes=1 << 20):
        Snapshot.take(snap, {"m": PytreeState(_state(total_bytes=16 << 20))})
    return snap


def test_analyze_cli_prints_verdict(tmp_path, capsys):
    snap = _probe_take(tmp_path)
    rc = main(["analyze", snap])
    out = capsys.readouterr().out
    assert rc == 0
    assert "BOUND BY:" in out
    assert "attribution" in out
    assert "storage-boundary latency" in out
    assert "roofline:" in out


def test_analyze_cli_json_shape(tmp_path, capsys):
    snap = _probe_take(tmp_path)
    rc = main(["analyze", snap, "--json"])
    assert rc == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["kind"] == "take"
    assert doc["bound_by"] in (
        "storage_write",
        "stage",
        "checksum",
        "dtoh",
    )
    assert 0 < doc["attribution"]["coverage"] <= 1
    assert "write.FSStoragePlugin" in doc["io_histograms"]
    assert isinstance(doc["findings"], list)
    assert "roofline_fraction" in doc


def test_analyze_cli_check_exit_codes(tmp_path, capsys):
    snap = _probe_take(tmp_path)
    # Impossible roofline bar -> the warn finding fires -> exit 2. (Not
    # 1.1: on a noisy disk a small take does measure >110% of its probe.)
    rc = main(["analyze", snap, "--check", "--min-roofline", "1e9"])
    assert rc == 2
    capsys.readouterr()
    # Thresholds that cannot fire -> healthy -> exit 0.
    rc = main(
        [
            "analyze",
            snap,
            "--check",
            "--min-roofline",
            "0",
            "--p99-ratio",
            "1e9",
            "--max-skew",
            "1e9",
        ]
    )
    assert rc == 0


def test_analyze_cli_zero_spans_exits_3(tmp_path, capsys):
    # Knob-off take: counters roll up but zero spans anywhere — the
    # doctor has nothing to attribute; one-liner + exit 3 like `trace`.
    with override_telemetry_enabled(False):
        Snapshot.take(str(tmp_path / "snap"), {"m": PytreeState(_state())})
    rc = main(["analyze", str(tmp_path / "snap")])
    captured = capsys.readouterr()
    assert rc == 3
    assert "no telemetry recorded" in captured.err


def test_analyze_cli_pre_telemetry_snapshot_exits_3(tmp_path, capsys):
    # Simulate a pre-telemetry snapshot: strip the trace sidecar and
    # the rollup extras from a committed snapshot.
    import shutil

    snap = str(tmp_path / "snap")
    Snapshot.take(snap, {"m": PytreeState(_state())})
    shutil.rmtree(os.path.join(snap, ".tpusnap", "telemetry"))
    md_path = os.path.join(snap, ".snapshot_metadata")
    from tpusnap.manifest import decode_metadata, encode_metadata

    md = decode_metadata(open(md_path, "rb").read())
    md.extras = {}
    with open(md_path, "wb") as f:
        f.write(encode_metadata(md))
    rc = main(["analyze", snap])
    captured = capsys.readouterr()
    assert rc == 3
    assert "no telemetry recorded" in captured.err


def test_analyze_cli_restore(tmp_path, capsys):
    from tpusnap.knobs import override_telemetry_dir

    snap = str(tmp_path / "snap")
    state = _state()
    Snapshot.take(snap, {"m": PytreeState(state)})
    with override_telemetry_dir(str(tmp_path / "tele")):
        target = {k: np.zeros_like(v) for k, v in state.items()}
        Snapshot(snap).restore({"m": PytreeState(target)})
        rc = main(["analyze", snap, "--restore", "--json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert doc["kind"] == "restore"
    assert doc["bound_by"] in ("storage_read", "consume")


def test_analyze_cli_history_context(tmp_path, capsys):
    from tpusnap.knobs import override_telemetry_dir

    with override_telemetry_dir(str(tmp_path / "tele")):
        snap = _probe_take(tmp_path)
        rc = main(["analyze", snap, "--history", "--json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert doc["history"]["events"] >= 1
    assert "throughput_gbps" in doc["history"]


def test_cli_help_lists_analyze(capsys):
    rc = main(["--help"])
    assert rc == 0
    assert "analyze" in capsys.readouterr().out


# ------------------------------------------------------- distributed


def _world_histogram_take(snap_dir):
    import jax.numpy as jnp

    from tpusnap import Snapshot, StateDict
    from tpusnap.comm import get_communicator

    comm = get_communicator()
    state = StateDict(
        w=jnp.arange(8192, dtype=jnp.float32) * (comm.rank + 1),
        b=jnp.ones(64, jnp.float32),
    )
    Snapshot.take(snap_dir, {"model": state})
    comm.barrier()
    if comm.rank == 0:
        per_rank_counts = []
        for r in range(comm.world_size):
            p = os.path.join(
                snap_dir, ".tpusnap", "telemetry", f"rank_{r}.json"
            )
            doc = json.load(open(p))
            hist = doc["summary"]["io_histograms"]
            per_rank_counts.append(hist["write.FSStoragePlugin"]["count"])
            assert per_rank_counts[-1] > 0, f"rank {r} recorded no writes"
        md = json.load(open(os.path.join(snap_dir, ".snapshot_metadata")))
        merged = md["extras"]["telemetry"]["io_histograms"][
            "write.FSStoragePlugin"
        ]
        # The rollup merge is the SUM of the per-rank histograms —
        # bucket counts included, so one rank's tail survives the fold.
        assert merged["count"] == sum(per_rank_counts), (
            merged,
            per_rank_counts,
        )
        assert merged["p99_s"] is not None


@pytest.mark.distributed
def test_distributed_histogram_merge_in_rollup(tmp_path):
    from tpusnap.test_utils import run_subprocess_world

    run_subprocess_world(
        _world_histogram_take, world_size=2, args=[str(tmp_path / "snap")]
    )


def _world_probe_restore(snap_dir):
    import numpy as np

    from tpusnap import PytreeState, Snapshot, telemetry
    from tpusnap.comm import get_communicator
    from tpusnap.knobs import override_probe
    from tpusnap.progress import load_restore_traces
    from tpusnap.telemetry import rollup_summaries

    comm = get_communicator()
    state = {"w": np.arange(1 << 21, dtype=np.uint8) + comm.rank}
    Snapshot.take(snap_dir, {"m": PytreeState(state)})
    comm.barrier()
    with override_probe(True, interval_bytes=1 << 20, probe_bytes=1 << 20):
        Snapshot(snap_dir).restore(
            {"m": PytreeState({"w": np.zeros(1 << 21, np.uint8)})}
        )
    s = telemetry.LAST_RESTORE_SUMMARY
    assert s.get("restore_roofline_fraction"), sorted(s)
    comm.barrier()
    if comm.rank == 0:
        # Every rank persisted a restore trace; the cross-rank fold
        # carries the read-lane fraction (fleet p50) and the probe
        # aggregate — what `analyze --restore` and the Prometheus
        # gauge read.
        docs = load_restore_traces(snap_dir)
        assert sorted(docs) == [0, 1], sorted(docs)
        roll = rollup_summaries([d["summary"] for d in docs.values()])
        assert roll["restore_roofline_fraction"] > 0
        assert roll["probe"]["read_gbps_p50"] > 0


@pytest.mark.distributed
def test_distributed_restore_rollup_carries_read_fraction(tmp_path):
    from tpusnap.test_utils import run_subprocess_world

    run_subprocess_world(
        _world_probe_restore,
        world_size=2,
        args=[str(tmp_path / "snap")],
        extra_env={"TPUSNAP_TELEMETRY_DIR": str(tmp_path / "teledir")},
    )
