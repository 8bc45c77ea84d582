"""The span record at telemetry's seam: what every span carries (start and
end on the monotonic clock, thread, kind, op, parent), wait and work told
apart at each thread hand-off, the DtoH and HtoD intervals, and the same
spans as annotations in the profiler's own trace. CPU only."""

import concurrent.futures
import glob
import json
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpusnap import PytreeState, Snapshot, metrics_sink, telemetry

KINDS = {telemetry.PHASE, telemetry.WAIT, telemetry.WORK}
NEW_SPANS = {
    "stage.queued", "stage.work", "write.queued", "write.work", "write.fsync",
    "read.queued", "read.work", "consume.queued", "decode", "htod", "dtoh.transfer",
}
# (the request's await span, the hand-off's spans recorded on the worker)
HANDOFFS = {
    "stage_buffer": ("stage.queued", "stage.work"),
    "storage_write": ("write.queued", "write.work", "write.fsync"),
    "storage_read": ("read.queued", "read.work"),
    "consume": ("consume.queued", "decode", "htod"),
}


class RecordSink(telemetry.MetricsSink):
    def __init__(self):
        self.records = []
        self.summaries = []

    def on_span_record(self, record):
        self.records.append(record)

    def on_take_summary(self, summary):
        self.summaries.append(summary)


class NameSink(telemetry.MetricsSink):
    """A sink as they were written before records: ``on_span`` alone."""

    def __init__(self):
        self.names = []

    def on_span(self, name, duration_s, attrs):
        assert duration_s >= 0 and isinstance(attrs, dict)
        self.names.append(name)


class DuckSink:
    """Not a ``MetricsSink`` at all (the benchmark's ``SpanLog`` is one
    such): every other ``on_*`` is answered by ``__getattr__``."""

    def __init__(self):
        self.names = []

    def on_span(self, name, duration_s, attrs):
        self.names.append(name)

    def __getattr__(self, name):
        if name.startswith("on_"):
            return lambda *a, **k: None
        raise AttributeError(name)


def _state():
    key = jax.random.PRNGKey(7)
    big = {
        f"w{i}": jax.random.normal(jax.random.fold_in(key, i), (1536, 1024), jnp.float32)
        for i in range(3)
    }  # 6 MiB each: over the slab threshold's reach and the native write's floor
    small = {f"b{i}": jnp.full((256,), float(i), jnp.float32) for i in range(6)}
    return {"big": big, "small": small}


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    """One async take and one restore of it, then a second take, with a
    record sink, an ``on_span``-only sink and a duck-typed sink listening."""
    root = tmp_path_factory.mktemp("records")
    state = _state()
    env = pytest.MonkeyPatch()
    env.setenv("TPUSNAP_DURABLE_COMMIT", "1")
    env.setenv("TPUSNAP_TELEMETRY", "1")
    env.setenv("TPUSNAP_TELEMETRY_DIR", str(root / "telemetry"))
    env.setenv("TPUSNAP_SLAB_SIZE_THRESHOLD_BYTES", str(1 << 20))
    telemetry.reset_global_counters()
    try:
        with metrics_sink(RecordSink()) as sink, metrics_sink(NameSink()) as names, \
                metrics_sink(DuckSink()) as duck:
            path = str(root / "snap")
            Snapshot.async_take(path, {"train": PytreeState(state)}).wait()
            enqueued = telemetry.counter_value("dtoh.enqueued_bytes")
            targets = {"train": PytreeState(jax.tree.map(jnp.zeros_like, state))}
            Snapshot(path).restore(targets)
            Snapshot.async_take(str(root / "snap2"), {"train": PytreeState(state)}).wait()
    finally:
        env.undo()
    restored = targets["train"].tree
    assert all(
        np.array_equal(a, b) for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored))
    )
    ops = list(dict.fromkeys(r.op for r in sink.records))
    return {
        "records": sink.records, "names": names.names, "duck": duck.names,
        "ops": ops, "state": state, "path": path, "enqueued": enqueued,
        "by_id": {r.id: r for r in sink.records},
    }


def _of(run, op, *names):
    return [r for r in run["records"] if r.op == op and r.name in names]


def test_every_record_carries_its_fields(run):
    now = time.monotonic()
    assert run["records"]
    for r in run["records"]:
        assert r.start <= r.end <= now, r
        assert r.kind in KINDS and r.thread and r.op in run["ops"], r
        assert r.duration_s == r.end - r.start
        if r.parent is not None:
            parent = run["by_id"][r.parent]
            assert parent.op == r.op, (r, parent)
    assert len(run["by_id"]) == len(run["records"])  # ids are unique
    by_name = {r.name: r for r in run["records"]}
    assert by_name["prepare"].kind == telemetry.PHASE
    assert by_name["storage_write"].kind == telemetry.WAIT
    assert by_name["write.work"].kind == telemetry.WORK


def test_one_op_per_take_and_restore(run):
    take1, restore, take2 = run["ops"]
    assert take1.startswith("take-") and take2.startswith("take-") and take1 != take2
    assert restore.startswith("restore-")
    for op, must in ((take1, "storage_write"), (restore, "storage_read"), (take2, "stage.work")):
        assert _of(run, op, must), (op, must)
    # Nothing of a take is recorded under another's op: every span's
    # interval lies inside its own op's first-to-last extent by construction,
    # and the phases of one op never repeat.
    for op in (take1, take2):
        assert len(_of(run, op, "prepare")) == 1


@pytest.mark.parametrize("request_span", sorted(HANDOFFS))
def test_handoff_spans_are_on_the_worker_inside_their_request(run, request_span):
    children = [r for r in run["records"] if r.name in HANDOFFS[request_span]]
    assert {r.name for r in children} >= set(HANDOFFS[request_span][:2])
    for r in children:
        parent = run["by_id"].get(r.parent)
        if parent is None:  # the commit's own small writes: no request span
            assert request_span == "storage_write" and r.name != "write.work"
            continue
        assert parent.name == request_span, (r, parent)
        assert r.thread != parent.thread and r.thread.startswith("tpusnap-"), r
        assert parent.start - 1e-3 <= r.start and r.end <= parent.end + 1e-3, (r, parent)
        assert r.kind == (telemetry.WAIT if r.name.endswith(".queued") else telemetry.WORK)


@pytest.fixture(scope="module")
def solo(tmp_path_factory):
    """A state of one leaf: one request of each kind in flight at a time,
    so that no other task's turn on the event loop stands between a
    request's await and its hand-off."""
    root = tmp_path_factory.mktemp("solo")
    state = {"w": jnp.arange(4 << 20, dtype=jnp.float32)}
    env = pytest.MonkeyPatch()
    env.setenv("TPUSNAP_DURABLE_COMMIT", "1")
    env.setenv("TPUSNAP_TELEMETRY", "1")
    env.setenv("TPUSNAP_TELEMETRY_DIR", str(root / "telemetry"))
    try:
        with metrics_sink(RecordSink()) as sink:
            Snapshot.async_take(str(root / "snap"), {"train": PytreeState(state)}).wait()
            targets = {"train": PytreeState({"w": jnp.zeros_like(state["w"])})}
            Snapshot(str(root / "snap")).restore(targets)
    finally:
        env.undo()
    return sink.records


@pytest.mark.parametrize("request_span", ["stage_buffer", "storage_write", "storage_read"])
def test_queue_plus_work_adds_up_to_the_await(solo, request_span):
    """Per request, what the worker recorded accounts for the await span
    in order: every part lies inside the request, no two overlap, each
    wait for a thread ends before the work it waited for starts, and
    their sum is no more than the await. (How far the sum falls short is
    the event loop's latency, a reading of the machine and not of the
    code: the benchmark reports it, no test bounds it.)"""
    seen = 0
    for req in (r for r in solo if r.name == request_span):
        parts = sorted(
            (r for r in solo if r.parent == req.id and r.name in HANDOFFS[request_span]),
            key=lambda r: r.start,
        )
        if not any(r.name.endswith(".work") for r in parts):
            continue  # a small blob on the aiofiles path: no executor hand-off
        seen += 1
        layout = [(r.name, r.start - req.start, r.end - req.start) for r in parts]
        for r in parts:
            assert req.start <= r.start <= r.end <= req.end, (req, layout)
        for a, b in zip(parts, parts[1:]):
            assert a.end <= b.start, layout
        # wait, work, wait, work: a hand-off's `.queued` is followed by its body
        assert len(parts) % 2 == 0, layout
        for queued, work in zip(parts[::2], parts[1::2]):
            assert queued.name.endswith(".queued") and queued.kind == telemetry.WAIT, layout
            assert not work.name.endswith(".queued") and work.kind == telemetry.WORK, layout
            assert queued.thread == work.thread, layout
        assert sum(r.duration_s for r in parts) <= req.duration_s + 1e-9, (req, layout)
    assert seen == 1


def test_dtoh_transfer_starts_before_staging_and_counts_the_bytes(run):
    take1 = run["ops"][0]
    transfers = _of(run, take1, "dtoh.transfer")
    leaves = [r for r in transfers if "slab_members" not in r.attrs]
    slabs = [r for r in transfers if "slab_members" in r.attrs]
    assert len(leaves) == 3 and slabs
    (prepare,) = _of(run, take1, "prepare")
    for r in leaves:
        work = run["by_id"][r.parent]
        # Started by the scheduler's dispatch: after prepare, which
        # starts none, and before the leaf's own staging.
        assert work.name == "stage.work" and prepare.end <= r.start < work.start, (r, work)
        assert r.kind == telemetry.WORK
    state = run["state"]
    small = sum(x.nbytes for x in jax.tree.leaves(state["small"]))
    big = sum(x.nbytes for x in jax.tree.leaves(state["big"]))
    assert sum(r.attrs["bytes"] for r in slabs) == small
    # Every leaf's copy is started (and counted) once, when the
    # scheduler's dispatch reaches it or a fixed depth before; the
    # members of a slab cross again inside it, and only the slab's
    # crossing is observed. So: the spans' bytes are the counter's, less
    # the members' prefetch, plus the slabs'.
    assert run["enqueued"] == big + small
    assert sum(r.attrs["bytes"] for r in transfers) == run["enqueued"] - small + small
    # The residual wait keeps its name and is no longer the transfer.
    dtoh = [r for r in _of(run, take1, "dtoh") if "slab_members" not in r.attrs]
    assert all(r.kind == telemetry.WAIT for r in dtoh) and len(dtoh) == 3


def test_htod_bytes_are_the_restored_targets(run):
    restore = run["ops"][1]
    htod = _of(run, restore, "htod")
    assert sum(r.attrs["bytes"] for r in htod) == sum(
        x.nbytes for x in jax.tree.leaves(run["state"])
    )
    assert all(r.thread.startswith("tpusnap-consume") for r in htod)
    assert _of(run, restore, "decode")


def test_sinks_written_before_records_get_every_span(run):
    want = sorted(r.name for r in run["records"])
    assert sorted(run["names"]) == want
    assert sorted(run["duck"]) == want
    assert NEW_SPANS <= set(want)


def test_persisted_trace_carries_kind_op_parent(run):
    with open(os.path.join(run["path"], ".tpusnap", "telemetry", "rank_0.json")) as f:
        doc = json.load(f)
    spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    meta = next(e for e in doc["traceEvents"] if e.get("ph") == "M")
    by_id = {e["args"]["id"]: e for e in spans}
    assert {e["args"]["op"] for e in spans} == {run["ops"][0]} == {doc["summary"]["op"]}
    for name in ("stage.work", "write.work", "write.fsync"):
        ev = next(e for e in spans if e["name"] == name)
        assert ev["args"]["kind"] == "work" and ev["tid"].startswith("tpusnap-")
        assert by_id[ev["args"]["parent"]]["name"] in ("stage_buffer", "storage_write")
    # ts is an offset; with the anchor it is the sink's absolute clock.
    rec = next(r for r in run["records"] if r.name == "prepare" and r.op == run["ops"][0])
    ev = next(e for e in spans if e["name"] == "prepare")
    assert meta["args"]["t0_monotonic"] + ev["ts"] / 1e6 == pytest.approx(rec.start, abs=1e-4)


def test_spans_opened_in_a_phase_take_it_as_parent(run):
    take1 = run["ops"][0]
    stage = next(r for r in _of(run, take1, "stage"))
    for r in _of(run, take1, "stage_buffer", "stage_blocked"):
        assert r.parent == stage.id
    assert stage.parent is None and stage.kind == telemetry.PHASE


def test_handoff_tells_the_queue_from_the_work():
    rec = telemetry.TakeTelemetry(rank=0, enabled=True)
    gate = threading.Event()
    with concurrent.futures.ThreadPoolExecutor(1, thread_name_prefix="w") as pool:
        blocker = pool.submit(gate.wait, 5)
        with rec.span("request", kind=telemetry.WAIT) as req:
            fut = pool.submit(rec.handoff("x", lambda: time.sleep(0.02) or 7, bytes=3))
            time.sleep(0.05)
            gate.set()
            assert fut.result(timeout=5) == 7 and blocker.result(timeout=5)
    rec.finalize()
    by_name = {e["name"]: e for e in rec.chrome_trace_events() if e.get("ph") == "X"}
    queued, work = by_name["x.queued"], by_name["x.work"]
    assert queued["dur"] >= 45e3 and 18e3 <= work["dur"] < 45e3
    assert queued["args"]["parent"] == work["args"]["parent"] == req.id
    assert work["args"]["bytes"] == 3 and work["tid"].startswith("w")
    # Off: the function itself, nothing recorded.
    off = telemetry.TakeTelemetry(rank=0, enabled=False)
    fn = lambda: 1  # noqa: E731
    assert off.handoff("x", fn) is fn


def test_telemetry_off_records_none_of_it(tmp_path, monkeypatch):
    monkeypatch.setenv("TPUSNAP_TELEMETRY", "0")
    monkeypatch.setenv("TPUSNAP_DURABLE_COMMIT", "1")
    state = _state()
    with metrics_sink(RecordSink()) as sink:
        path = str(tmp_path / "snap")
        Snapshot.async_take(path, {"train": PytreeState(state)}).wait()
        targets = {"train": PytreeState(jax.tree.map(jnp.zeros_like, state))}
        Snapshot(path).restore(targets)
    assert sink.records == []
    assert sink.summaries and sink.summaries[0]["stages"] == {}
    assert Snapshot(path).verify().clean
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(targets["train"].tree)):
        assert np.array_equal(a, b)


def test_profiler_trace_holds_tpusnap_events_on_worker_lines(tmp_path, monkeypatch):
    """A plain ``jax.profiler.trace`` of one small take: the phases on the
    caller's line, the work spans on other lines, no anchor, no sink."""
    from jax.profiler import ProfileData

    monkeypatch.setenv("TPUSNAP_DURABLE_COMMIT", "1")
    monkeypatch.setenv("TPUSNAP_TELEMETRY", "1")
    state = _state()
    with jax.profiler.trace(str(tmp_path / "trace")):
        Snapshot.async_take(str(tmp_path / "snap"), {"train": PytreeState(state)}).wait()
    (path,) = glob.glob(str(tmp_path / "trace" / "plugins" / "profile" / "*" / "*.xplane.pb"))
    lines = {}
    for plane in ProfileData.from_file(path).planes:
        for i, ln in enumerate(plane.lines):
            for ev in ln.events:
                if ev.name.startswith("tpusnap:"):
                    lines.setdefault(ev.name, set()).add((plane.name, i))
    caller = lines["tpusnap:prepare"]
    assert len(caller) == 1 and lines["tpusnap:stage"] == caller
    for name in ("tpusnap:stage.work", "tpusnap:write.work", "tpusnap:write.fsync"):
        assert lines[name] and not (lines[name] & caller), (name, lines[name])
    # Waits interleave on the event loop's thread and are no annotations.
    assert "tpusnap:storage_write" not in lines and "tpusnap:stage.queued" not in lines


def test_leaves_staged_after_the_blocked_window_keep_their_spans(tmp_path, monkeypatch):
    """``async_take`` releases the global recorder when it returns, and a
    staging thread has no recorder of its own: the hand-off installs the
    request's, so the drain's ``dtoh`` / ``checksum`` spans are recorded
    like the blocked window's (they used to be dropped)."""
    monkeypatch.setenv("TPUSNAP_TELEMETRY", "1")
    monkeypatch.setenv("TPUSNAP_ASYNC_STAGE_WINDOW_BYTES", str(8 << 20))  # one leaf
    monkeypatch.setenv("TPUSNAP_SLAB_SIZE_THRESHOLD_BYTES", str(1 << 20))
    key = jax.random.PRNGKey(3)
    state = {f"w{i}": jax.random.normal(jax.random.fold_in(key, i), (2048, 1024)) for i in range(5)}
    with metrics_sink(RecordSink()) as sink:
        Snapshot.async_take(str(tmp_path / "snap"), {"train": PytreeState(state)}).wait()
    blocked = next(r for r in sink.records if r.name == "async_blocked")
    work = [r for r in sink.records if r.name == "stage.work"]
    assert len(work) == 5 and sum(r.start > blocked.end for r in work) >= 3
    for name in ("dtoh", "dtoh.transfer"):
        assert sum(r.name == name for r in sink.records) == 5, name
    assert sum(r.name in ("checksum", "checksum_late") for r in sink.records) >= 5
