"""What kept the thread that called a take, and the loop that drives it,
from running: ``<name>.resumed`` (the loop's lateness for a request that had
finished, measured), the sampled holder on the caller's and the loop's
thread (``telemetry.HolderWatch`` on the RSS sampler's thread), and the gate
(a sink registered when the operation began; ``TPUSNAP_TELEMETRY``). CPU
only; no time read here is a device's."""

import asyncio
import concurrent.futures
import contextlib
import json
import os
import re
import subprocess
import sys
import threading
import time
import types

import jax
import jax.numpy as jnp
import pytest

from tpusnap import PytreeState, Snapshot, metrics_sink, telemetry
from tpusnap.rss_profiler import RSSSampler
from tpusnap.snapshot import PendingSnapshot

HOLDER_COUNTERS = ("caller.", "loop.", "watch.", "take.process_cpu_us")


class Sink(telemetry.MetricsSink):
    def __init__(self):
        self.records = []
        self.counters = {}

    def on_span_record(self, record):
        self.records.append(record)

    def on_counter(self, name, delta, value):
        self.counters[name] = self.counters.get(name, 0) + delta

    def named(self, name):
        return [r for r in self.records if r.name == name]

    def holder_counters(self):
        return {k: v for k, v in self.counters.items() if k.startswith(HOLDER_COUNTERS)}


# ---- <name>.resumed


def _trip(rec, name, work, body_s, block_loop_s):
    """One request through ``run_handoff`` on a fresh loop: the worker's
    body sleeps ``body_s``; a callback blocks the loop's thread for
    ``block_loop_s`` from the moment the request is submitted."""
    pool = concurrent.futures.ThreadPoolExecutor(1, thread_name_prefix="tpusnap-test")

    async def request():
        with rec.span("storage_write", kind=telemetry.WAIT) as sp:
            loop = asyncio.get_running_loop()
            if block_loop_s:
                loop.call_soon(time.sleep, block_loop_s)
            value = await telemetry.run_handoff(
                pool, name, lambda x: time.sleep(body_s) or x, 7, work=work, bytes=3)
        return sp.id, value

    loop = asyncio.new_event_loop()
    try:
        with telemetry.use(rec):
            return loop.run_until_complete(request())
    finally:
        loop.close()
        pool.shutdown()


@pytest.mark.parametrize("name,work,work_span", [
    ("write", True, "write.work"), ("write", "write.fsync", "write.fsync"),
    ("read", True, "read.work"), ("stage", True, "stage.work"), ("consume", False, None),
])
def test_resumed_is_on_the_loops_thread_from_the_works_end(name, work, work_span):
    rec = telemetry.TakeTelemetry(0, enabled=True)
    try:
        request_id, value = _trip(rec, name, work, body_s=0.05, block_loop_s=0.15)
    finally:
        rec.close()
    assert value == 7
    spans = {r.name: r for r in rec._spans}
    resumed, queued, request = spans[f"{name}.resumed"], spans[f"{name}.queued"], spans["storage_write"]
    assert resumed.kind == telemetry.WAIT and resumed.parent == request_id == request.id
    assert resumed.thread == threading.current_thread().name != queued.thread
    assert queued.thread.startswith("tpusnap-test")
    if work_span:
        body = spans[work_span]
        assert resumed.start == body.end and body.thread == queued.thread
        assert body.attrs["bytes"] == 3
    else:  # a body that records its own spans: stamped as it returns
        assert queued.end + 0.05 <= resumed.start <= queued.end + 0.12
    # The loop was blocked 0.15 s from the submit and the body took 0.05 s:
    # the request had been finished for 0.1 s when the loop came back.
    assert 0.08 <= resumed.duration_s <= 0.3
    assert resumed.end <= request.end


@pytest.mark.parametrize("block_loop_s", [0.0, 0.12])
def test_resumed_closes_the_await_spans_sum(block_loop_s):
    rec = telemetry.TakeTelemetry(0, enabled=True)
    try:
        _trip(rec, "write", True, body_s=0.04, block_loop_s=block_loop_s)
    finally:
        rec.close()
    spans = {r.name: r for r in rec._spans}
    parts = [spans[n] for n in ("write.queued", "write.work", "write.resumed")]
    for earlier, later in zip(parts, parts[1:]):
        assert earlier.end <= later.start + 1e-9
    residue = spans["storage_write"].duration_s - sum(p.duration_s for p in parts)
    assert 0 <= residue < 0.02, residue
    if block_loop_s:  # without the new span a remainder of 0.08 s had no name
        assert spans["write.resumed"].duration_s >= 0.06


def test_run_handoff_with_no_recorder_or_spans_off_is_the_bare_call():
    pool = concurrent.futures.ThreadPoolExecutor(1)
    off = telemetry.TakeTelemetry(0, enabled=False)

    async def request():
        plain = await telemetry.run_handoff(pool, "write", lambda a, b: a + b, 1, 2)
        tracked = await telemetry.run_handoff(
            pool, "read", lambda: 5, submit=lambda ex, fn: asyncio.wrap_future(ex.submit(fn)))
        with telemetry.use(off):
            silent = await telemetry.run_handoff(pool, "stage", lambda: 9)
        return plain, tracked, silent

    loop = asyncio.new_event_loop()
    try:
        assert loop.run_until_complete(request()) == (3, 5, 9)
    finally:
        loop.close()
        pool.shutdown()
    assert off._spans == []


def test_a_failed_body_still_says_when_the_loop_came_back():
    rec = telemetry.TakeTelemetry(0, enabled=True)
    pool = concurrent.futures.ThreadPoolExecutor(1)

    def body():
        raise OSError("disk")

    async def request():
        await telemetry.run_handoff(pool, "write", body)

    loop = asyncio.new_event_loop()
    try:
        with telemetry.use(rec), pytest.raises(OSError):
            loop.run_until_complete(request())
    finally:
        loop.close()
        pool.shutdown()
        rec.close()
    assert [r.name for r in rec._spans] == ["write.queued", "write.work", "write.resumed"]


# ---- the classes


class Parked:
    """A thread that stands, one after the other, in user code, under
    ``jax.block_until_ready``, under ``jax.device_put`` and inside
    ``PendingSnapshot.wait_staged()``, each until it is let go."""

    STANDS = ("other", "wait_device", "transfer", "tpusnap")

    def __init__(self, monkeypatch):
        from jax._src import dispatch

        self.gates = {name: threading.Event() for name in self.STANDS}
        self.key = None
        self.standing = threading.Event()
        # `device_put` reaches the runtime through this function; a stand-in
        # compiled under jax's own directory parks the thread there without
        # a frame of the test's between `device_put` and the wait.
        inner = compile(
            "def _batched_device_put_impl(*xs, **kw):\n    gate.wait()\n    return list(xs)\n",
            os.path.join(os.path.dirname(dispatch.__file__), "dispatch.py"), "exec")
        scope = {"gate": self.gates["transfer"]}
        exec(inner, scope)
        monkeypatch.setattr(dispatch, "_batched_device_put_impl", scope["_batched_device_put_impl"])
        self.thread = threading.Thread(target=self.run, name="the-caller", daemon=True)
        self.thread.start()
        self.standing.wait(5)

    def run(self):
        self.key = telemetry.thread_key()
        self.standing.set()
        self.gates["other"].wait()  # user code: this frame
        # A leaf that is no jax array is asked for its own block_until_ready.
        jax.block_until_ready(types.SimpleNamespace(block_until_ready=self.gates["wait_device"].wait))
        jax.device_put(1.0)
        pending = PendingSnapshot.__new__(PendingSnapshot)
        pending._done = threading.Event()
        pending._pending_io_work = types.SimpleNamespace(
            wait_staged=self.gates["tpusnap"].wait, caller_waits=contextlib.nullcontext,
            went_cow=lambda: False)
        pending.wait_staged()

    def frame(self):
        return sys._current_frames()[self.thread.ident]

    def release(self, name):
        self.gates[name].set()

    def finish(self):
        for gate in self.gates.values():
            gate.set()
        self.thread.join(5)


@pytest.fixture
def parked(monkeypatch):
    p = Parked(monkeypatch)
    yield p
    p.finish()


def _wait_for_class(parked, want):
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:
        cls, site = telemetry.classify_caller(parked.frame())
        if cls == want:
            return telemetry._site_name(site)
        time.sleep(0.005)
    raise AssertionError(f"never stood in {want}")


def test_the_callers_stack_is_classed_into_the_closed_vocabulary(parked):
    here = os.path.basename(__file__)
    assert _wait_for_class(parked, "other").startswith(f"{here}:run:")
    parked.release("other")
    # The site is the innermost frame outside jax and the standard library:
    # the line of the caller's own code that called into jax.
    assert _wait_for_class(parked, "wait_device").startswith(f"{here}:run:")
    parked.release("wait_device")
    assert _wait_for_class(parked, "transfer").startswith(f"{here}:run:")
    parked.release("transfer")
    assert _wait_for_class(parked, "tpusnap").startswith("snapshot.py:wait_staged:")


def test_a_frames_code_is_told_by_where_its_file_lies():
    import asyncio.base_events
    import selectors

    import psutil

    kinds = {
        telemetry._F_TPUSNAP: Snapshot.restore, telemetry._F_USER: _wait_for_class,
        telemetry._F_THIRD: psutil.Process.memory_info, telemetry._F_LIB: threading.Event.wait,
        telemetry._F_WAIT: jax.block_until_ready, telemetry._F_PUT: jax.device_put,
        telemetry._F_SELECT: selectors.DefaultSelector.select,
        telemetry._F_RUN_ONCE: asyncio.base_events.BaseEventLoop._run_once,
    }
    for kind, fn in kinds.items():
        assert telemetry._frame_kind(fn.__code__) == kind, fn
    # An installed package between the package and the wait (psutil under a
    # restore's memory budget) does not make the caller's class `other`.
    frame = types.SimpleNamespace(
        f_code=psutil.Process.memory_info.__code__, f_lineno=1,
        f_back=types.SimpleNamespace(f_code=Snapshot.restore.__code__, f_lineno=2, f_back=None))
    assert telemetry.classify_caller(frame)[0] == "tpusnap"
    frame.f_back = None
    cls, site = telemetry.classify_caller(frame)
    assert cls == "other" and "psutil" in site[0].co_filename and site[1] == 1


def test_the_loops_stack_is_idle_in_the_selector_and_a_callback_elsewhere():
    seen, stop = [], threading.Event()
    loop = asyncio.new_event_loop()

    def spin():  # a callback of known length
        deadline = time.monotonic() + 0.2
        while time.monotonic() < deadline:
            pass

    thread = threading.Thread(target=loop.run_forever, daemon=True)
    thread.start()
    try:
        time.sleep(0.05)
        seen.append(telemetry.classify_loop(sys._current_frames()[thread.ident]))
        loop.call_soon_threadsafe(spin)
        time.sleep(0.08)
        seen.append(telemetry.classify_loop(sys._current_frames()[thread.ident]))
    finally:
        loop.call_soon_threadsafe(loop.stop)
        thread.join(5)
        loop.close()
    assert seen[0] == ("idle", None)
    assert seen[1][0] == "callback"
    assert telemetry._site_name(seen[1][1]).startswith(f"{os.path.basename(__file__)}:spin:")
    # A thread that runs no loop is neither.
    assert telemetry.classify_loop(sys._current_frames()[threading.get_ident()]) == (None, None)


# ---- the watch


def _watched(caller=None):
    """A recorder as a take has it, begun with a sink registered."""
    sink = Sink()
    telemetry.register_metrics_sink(sink)
    rec = telemetry.TakeTelemetry(0, enabled=True, caller=caller)
    rec.meta["kind"] = "take"
    return rec, sink


def test_ticks_of_one_class_merge_into_one_span_and_the_spans_tile(parked):
    rec, sink = _watched(caller=parked.key)
    try:
        t_begin = time.monotonic()
        rec.watch_begin()
        for name in Parked.STANDS:
            _wait_for_class(parked, name)
            time.sleep(0.15)
            parked.release(name)
        parked.thread.join(5)
        time.sleep(0.03)  # the caller's thread is gone: nothing is recorded of it
    finally:
        rec.close()
        telemetry.unregister_metrics_sink(sink)
    t_end = time.monotonic()
    spans = [r for r in sink.records if r.name.startswith("caller.")]
    long = [r for r in spans if r.attrs["samples"] >= 5]
    assert [r.name for r in long] == [f"caller.{n}" for n in Parked.STANDS]
    for r in long:
        assert r.kind == telemetry.WAIT and r.thread == "caller" and r.parent is None
        assert r.duration_s >= 0.1 and r.attrs["thread"] == parked.key[1]
        assert r.attrs["site"].count(":") == 2
    assert long[0].attrs["site"].startswith(f"{os.path.basename(__file__)}:run:")
    assert long[3].attrs["site"].startswith("snapshot.py:wait_staged:")
    # The first span starts where the watch began; each starts where the one
    # before it ended; nothing is recorded of a thread that has ended.
    assert t_begin <= spans[0].start <= t_begin + 0.02
    for earlier, later in zip(spans, spans[1:]):
        assert later.start == earlier.end
    assert spans[-1].end <= t_end
    covered = sum(r.duration_s for r in spans)
    assert covered >= 0.6
    assert sink.counters["watch.samples"] >= 40
    assert sink.counters["take.process_cpu_us"] > 0 and sink.counters["watch.cpu_us"] >= 0
    assert not any(t.name == "tpusnap-rss" for t in threading.enumerate())


def test_a_thread_kept_off_the_lock_shows_as_late_ticks():
    rec, sink = _watched()
    try:
        rec.watch_begin()
        time.sleep(0.05)
        quiet = sink.counters.get("watch.late_us", 0)
        t = time.monotonic()
        sum(range(40_000_000))  # one call into C that keeps the lock throughout
        held_s = time.monotonic() - t
        time.sleep(0.03)
    finally:
        rec.close()
        telemetry.unregister_metrics_sink(sink)
    late_s = (sink.counters["watch.late_us"] - quiet) / 1e6
    assert held_s > 0.1 and late_s >= 0.5 * held_s, (held_s, late_s)
    assert rec.summary()["gauges"]["watch.late_max_us"] >= 0.5 * held_s * 1e6


def test_flush_hands_the_open_span_over_and_it_goes_on(parked):
    rec, sink = _watched(caller=parked.key)
    try:
        rec.watch_begin()
        time.sleep(0.08)
        rec.finalize()  # a take persists its trace here, before its end
        persisted = [r for r in rec._spans if r.name == "caller.other"]
        assert len(persisted) == 1 and persisted[0].attrs["samples"] >= 3
        assert any(t.name == "tpusnap-rss" for t in threading.enumerate())
        time.sleep(0.08)
    finally:
        rec.close()
        telemetry.unregister_metrics_sink(sink)
    first, second = sink.named("caller.other")
    assert second.start == first.end and second.attrs["samples"] >= 3
    assert "peak_rss_delta_bytes" in rec.summary()["gauges"]


def test_clocks_the_platform_does_not_give_leave_their_counters_out(monkeypatch, parked):
    real_open = os.open

    def no_schedstat(path, *args, **kwargs):
        if str(path).endswith("schedstat"):
            raise FileNotFoundError(path)
        return real_open(path, *args, **kwargs)

    monkeypatch.setattr(os, "open", no_schedstat)
    rec, sink = _watched(caller=parked.key[:2] + (None,))  # no CPU-time clock either
    try:
        rec.watch_begin()
        time.sleep(0.08)
    finally:
        rec.close()
        telemetry.unregister_metrics_sink(sink)
    (span,) = sink.named("caller.other")
    assert span.attrs["samples"] >= 3
    assert "cpu_ms" not in span.attrs and "runq_ms" not in span.attrs
    assert not [k for k in sink.counters if k.startswith("caller.")]
    assert sink.counters["watch.samples"] >= 3


@pytest.mark.skipif(not os.path.exists(f"/proc/self/task/{threading.get_native_id()}/schedstat"),
                    reason="this kernel gives no schedstat")
def test_a_busy_callers_cpu_time_is_counted():
    stop = threading.Event()
    key = []

    def burn():
        key.append(telemetry.thread_key())
        while not stop.is_set():
            pass

    thread = threading.Thread(target=burn, daemon=True)
    thread.start()
    while not key:
        time.sleep(0.001)
    rec, sink = _watched(caller=key[0])
    try:
        rec.watch_begin()
        time.sleep(0.25)
    finally:
        rec.close()
        telemetry.unregister_metrics_sink(sink)
        stop.set()
        thread.join(5)
    spans = sink.named("caller.other")
    assert sum(r.attrs["cpu_ms"] for r in spans) > 20  # it spun, lock or no lock
    assert sink.counters["caller.cpu_us"] > 20_000
    assert sink.counters["caller.cpu_us"] + sink.counters.get("caller.runq_us", 0) > 100_000


# ---- the sampler's thread


class Rider:
    def __init__(self):
        self.period_s = None
        self.ticks = []
        self.ended_on = None

    def tick(self, now, late_s):
        self.ticks.append((now, late_s, threading.current_thread().name))

    def end(self):
        self.ended_on = threading.current_thread().name


def test_a_rider_ticks_at_its_period_and_rss_is_read_at_its_own():
    rider = Rider()
    sampler = RSSSampler(interval_sec=0.05, rider=rider).start()
    before = threading.active_count()
    time.sleep(0.16)
    asleep = len(sampler.deltas)
    assert not rider.ticks and 2 <= asleep <= 4
    rider.period_s = 0.005
    sampler.poke()
    time.sleep(0.2)
    assert threading.active_count() == before
    sampler.stop()
    assert len(rider.ticks) >= 15
    assert all(name == "tpusnap-rss" and late >= 0 for _, late, name in rider.ticks)
    assert rider.ticks[0][0] <= rider.ticks[1][0]
    assert 3 <= len(sampler.deltas) - asleep <= 7  # every 50 ms still, and once at the stop
    assert rider.ended_on == "tpusnap-rss"
    assert not any(t.name == "tpusnap-rss" for t in threading.enumerate())


# ---- a take and a restore, end to end


def _state():
    return {f"w{i}": jnp.arange(2 << 20, dtype=jnp.float32) + i for i in range(3)}


@pytest.fixture(scope="module")
def watched_run(tmp_path_factory):
    root = tmp_path_factory.mktemp("holder")
    env = pytest.MonkeyPatch()
    env.setenv("TPUSNAP_DURABLE_COMMIT", "1")
    env.setenv("TPUSNAP_TELEMETRY", "1")
    env.setenv("TPUSNAP_TELEMETRY_DIR", str(root / "telemetry"))
    state = _state()
    threads = set()
    try:
        with metrics_sink(Sink()) as sink:
            t_call = time.monotonic()
            pending = Snapshot.async_take(str(root / "snap"), {"train": PytreeState(state)})
            t_returned = time.monotonic()
            while not pending.done():
                threads |= {t.name for t in threading.enumerate()}
                jax.block_until_ready(jnp.sum(state["w0"]))
            pending.wait()
            t_durable = time.monotonic()
            n_take = len(sink.records)
            targets = {"train": PytreeState({k: jnp.zeros_like(v) for k, v in state.items()})}
            Snapshot(str(root / "snap")).restore(targets)
    finally:
        env.undo()
    return {"sink": sink, "take": sink.records[:n_take], "restore": sink.records[n_take:],
            "times": (t_call, t_returned, t_durable), "threads": threads,
            "path": str(root / "snap"), "telemetry_dir": str(root / "telemetry")}


def test_a_watched_take_tiles_the_drain_with_the_callers_spans(watched_run):
    _, t_returned, t_durable = watched_run["times"]
    spans = sorted((r for r in watched_run["take"] if r.name.startswith("caller.")),
                   key=lambda r: r.start)
    assert spans and {r.name for r in spans} <= {
        "caller.other", "caller.wait_device", "caller.transfer", "caller.tpusnap"}
    assert abs(spans[0].start - t_returned) < 0.05 and spans[-1].end <= t_durable
    covered = sum(r.duration_s for r in spans)
    assert covered >= 0.95 * (spans[-1].end - spans[0].start)
    assert covered >= 0.8 * (t_durable - t_returned)
    loops = [r for r in watched_run["take"] if r.name.startswith("loop.")]
    assert loops and {r.name for r in loops} <= {"loop.idle", "loop.callback"}
    assert {r.thread for r in loops} == {"loop"} and {r.thread for r in spans} == {"caller"}
    # The drain's loop runs on the commit thread, not on the caller's.
    assert {r.attrs["thread"] for r in loops}.isdisjoint({r.attrs["thread"] for r in spans})


def test_a_watched_take_and_restore_record_every_resumed_leg(watched_run):
    take = {r.name for r in watched_run["take"]}
    restore = {r.name for r in watched_run["restore"]}
    assert {"stage.resumed", "write.resumed"} <= take
    assert {"read.resumed", "consume.resumed"} <= restore
    by_id = {r.id: r for r in watched_run["sink"].records}
    for r in watched_run["sink"].records:
        if r.name.endswith(".resumed") and r.parent is not None:
            request = by_id[r.parent]
            assert request.name in ("stage_buffer", "storage_write", "storage_read", "consume")
            assert request.thread == r.thread and r.end <= request.end + 1e-6
    # A restore that the caller's own thread runs: the caller stands inside
    # the package, and the loop's thread is the caller's.
    caller = [r for r in watched_run["restore"] if r.name.startswith("caller.")]
    loops = [r for r in watched_run["restore"] if r.name.startswith("loop.")]
    assert {r.name for r in caller} == {"caller.tpusnap"} and loops
    assert {r.attrs["thread"] for r in caller} == {r.attrs["thread"] for r in loops}


def test_the_counters_of_a_watched_take(watched_run):
    counters = watched_run["sink"].holder_counters()
    assert counters["watch.samples"] >= 3 and counters["take.process_cpu_us"] > 0
    assert "watch.cpu_us" in counters
    summary = telemetry.LAST_TAKE_SUMMARY
    assert summary["counters"]["take.process_cpu_us"] > 0
    assert "tpusnap-rss" in watched_run["threads"]


def test_the_trace_command_prints_what_held_the_two_threads(watched_run):
    with open(os.path.join(watched_run["path"], ".tpusnap", "telemetry", "rank_0.json")) as f:
        doc = json.load(f)
    rows = {ev["tid"] for ev in doc["traceEvents"] if ev.get("ph") == "X"}
    assert {"caller", "loop"} <= rows
    table = telemetry.holder_table(doc["traceEvents"], doc["summary"])
    assert table["caller"] and table["loop"] and "write.resumed" in table["resumed"]
    for row in table["caller"].values():
        assert row["seconds"] > 0 and len(row["sites"]) <= 3
    assert telemetry.holder_table([], {}) == {}
    env = dict(os.environ, JAX_PLATFORMS="cpu", TPUSNAP_TELEMETRY_DIR=watched_run["telemetry_dir"])
    for extra, title in (((), "while the take drained (rank 0):"),
                         (("--restore",), "while the restore ran (rank 0):")):
        proc = subprocess.run(
            [sys.executable, "-m", "tpusnap", "trace", *extra, watched_run["path"]],
            capture_output=True, text=True, timeout=120, env=env)
        assert proc.returncode == 0, proc.stderr[-2000:]
        tail = proc.stdout[proc.stdout.index(title):]
        assert "the caller's thread:" in tail and "the loop's thread:" in tail
        assert "finished requests waiting for the loop" in tail and "watch:" in tail
    proc = subprocess.run(
        [sys.executable, "-m", "tpusnap", "trace", "--json", watched_run["path"]],
        capture_output=True, text=True, timeout=120, env=env)
    assert json.loads(proc.stdout)["holder"]["0"]["caller"]


# ---- the gate


def _take_and_watch_threads(path):
    """The names of the threads alive during one async take, a pool's
    workers under the pool's name (how many of them start is the pool's
    business), and when its RSS was read."""
    names, rss_reads = set(), []
    real_sample = RSSSampler.sample

    def sample(self):
        rss_reads.append(time.monotonic())
        real_sample(self)

    env = pytest.MonkeyPatch()
    env.setattr(RSSSampler, "sample", sample)
    try:
        pending = Snapshot.async_take(path, {"train": PytreeState(_state())})
        while not pending.done():
            names |= {re.sub(r"_\d+$", "", t.name) for t in threading.enumerate()}
            time.sleep(0.002)
        pending.wait()
    finally:
        env.undo()
    return names, rss_reads


def test_with_no_sink_nothing_ticks_faster_and_no_thread_is_added(tmp_path, monkeypatch):
    monkeypatch.setenv("TPUSNAP_TELEMETRY", "1")
    ticks = []
    monkeypatch.setattr(telemetry.HolderWatch, "tick",
                        lambda self, now, late: ticks.append(now))
    before = {t.name for t in threading.enumerate()}
    plain, reads = _take_and_watch_threads(str(tmp_path / "plain"))
    assert not ticks
    assert all(b - a >= 0.09 for a, b in zip(reads, reads[1:-1])), reads
    with metrics_sink(Sink()) as sink:
        watched, _ = _take_and_watch_threads(str(tmp_path / "watched"))
    assert ticks and not sink.named("caller.other")  # the patched tick records nothing
    # The watch rides the thread a take has anyway: a sink adds no thread.
    assert watched - before == plain - before
    assert "tpusnap-rss" in plain
    assert {t.name for t in threading.enumerate()} - before == set()
    summary = telemetry.LAST_TAKE_SUMMARY
    assert "take.process_cpu_us" in summary["counters"]


def test_an_unwatched_recorder_reads_rss_every_100_ms_and_no_oftener():
    reads = []
    real_sample = RSSSampler.sample
    env = pytest.MonkeyPatch()
    env.setattr(RSSSampler, "sample", lambda self: reads.append(time.monotonic()) or real_sample(self))
    try:
        rec = telemetry.TakeTelemetry(0, enabled=True)
        assert rec._watch is None
        rec.watch_begin()  # nothing to begin
        rec.note_loop_thread()
        time.sleep(0.35)
        rec.close()
    finally:
        env.undo()
    assert 3 <= len(reads) <= 5
    assert all(b - a >= 0.09 for a, b in zip(reads, reads[1:-1]))
    assert not [k for k in rec.summary()["counters"] if k.startswith(HOLDER_COUNTERS)]


def test_telemetry_off_records_none_of_it(tmp_path, monkeypatch):
    monkeypatch.setenv("TPUSNAP_TELEMETRY", "0")
    names = set()
    with metrics_sink(Sink()) as sink:
        pending = Snapshot.async_take(str(tmp_path / "snap"), {"train": PytreeState(_state())})
        while not pending.done():
            names |= {t.name for t in threading.enumerate()}
            time.sleep(0.002)
        pending.wait()
        targets = {"train": PytreeState({k: jnp.zeros_like(v) for k, v in _state().items()})}
        Snapshot(str(tmp_path / "snap")).restore(targets)
    assert sink.records == [] and sink.holder_counters() == {}
    assert "tpusnap-rss" not in names


def test_the_span_callback_is_resolved_when_the_sink_is_registered():
    class Named(telemetry.MetricsSink):
        def on_span(self, name, duration_s, attrs):
            pass

    class Duck:
        def on_span(self, name, duration_s, attrs):
            pass

    for sink, want in ((Sink(), "on_span_record"), (Named(), "on_span"), (Duck(), "on_span")):
        with metrics_sink(sink):
            assert (sink, want) in telemetry._span_sinks
        assert all(s is not sink for s, _ in telemetry._span_sinks)
    assert len(telemetry._span_sinks) == len(telemetry._sinks)
