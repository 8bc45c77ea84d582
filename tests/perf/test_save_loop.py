"""The traffic kind ``save_loop`` off the chip: its loop driven in this
process over a step and a take that only sleep, so that what it stamps can
be held against what is known (when a take really ended, how many saves the
mix's file gives a window, how long the loop had to wait), and one
rehearsal of a second through ``perf/run.py``. CPU only; no time read here
stands for a device's."""

import json
import os
import statistics
import subprocess
import sys
import threading
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PERF = os.path.join(ROOT, "perf")
sys.path.insert(0, ROOT)

from perf import harness  # noqa: E402
from perf.traffic import save_loop  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
MIXES = sorted({w["traffic"] for w in MANIFEST["workloads"]
                if harness.read_json("traffic", f"{w['traffic']}.json")["kind"] == "save_loop"})


def saves_a_window(mix, seconds):
    """The k-th save is due ``first_save_s + k * save_every_s`` into the window."""
    first, every = float(mix["first_save_s"]), float(mix["save_every_s"])
    return sum(1 for k in range(1000) if first + k * every < seconds)


class SleepingTake:
    """Stands where ``PendingSnapshot`` does: durable after ``seconds``."""

    def __init__(self, seconds):
        self.t_call = time.monotonic()
        self.t_ended = None
        self._thread = threading.Thread(target=self._body, args=(seconds,), daemon=True)
        self._thread.start()

    def _body(self, seconds):
        time.sleep(seconds)
        self.t_ended = time.monotonic()

    def done(self):
        return not self._thread.is_alive()

    def wait(self):
        self._thread.join()


class Loop:
    """What ``save_loop.run`` asks of the harness's ``Context``, with a step
    that sleeps ``step_s`` and takes that sleep ``take_s[k]`` (the last one
    over again once the list ends)."""

    batch, seq_len, work_dir = 1, 8, "/nonexistent/perf_save_loop_test"

    def __init__(self, params, step_s, take_s):
        self.params, self.step_s, self.take_s = params, step_s, list(take_s)
        self.tracer = harness.Tracer(False, "")
        self.state, self.takes, self.said = 0, [], []

    def async_take(self, path, app_state, **kwargs):
        seconds = self.take_s[min(len(self.takes), len(self.take_s) - 1)]
        self.takes.append(SleepingTake(seconds))
        return self.takes[-1]

    def train_step(self, state, tokens):
        time.sleep(self.step_s)
        return state + 1, 0.0

    def next_tokens(self):
        return None

    def put_tokens(self, tokens):
        return tokens

    def app_state(self, tree):
        return {"train": tree}

    def take_kwargs(self):
        return {}

    def remove_later(self, path):
        pass

    def say(self, label, **fields):
        self.said.append((label, fields))


def drive(monkeypatch, params, seconds, step_s, take_s):
    loop = Loop(dict(trace_lead_s=0.1, trace_max_s=1.0, **params), step_s, take_s)
    monkeypatch.setattr(save_loop, "Snapshot", loop)
    result = save_loop.run(loop, seconds)
    assert not loop.said and result["failed"] == 0
    return loop, result


@pytest.mark.parametrize("take_s", [0.13, 0.25, 0.37])
def test_a_save_is_stamped_when_it_becomes_durable_not_when_the_loop_looks(monkeypatch, take_s):
    """Steps of 100 ms and takes that end in the middle of one: the stamp
    lies at the take's own end, and the loop learns of it at the step's end,
    later by what is left of that step and never by more."""
    step_s = 0.1
    loop, result = drive(monkeypatch, {"first_save_s": 0.05, "save_every_s": 0.7}, 2.0, step_s,
                         [take_s])
    assert result["attempted"] == result["saves_durable"] == len(loop.takes) == 3
    for take, durable, late_ms in zip(loop.takes, result["durable_s"],
                                      result["durable_seen_late_ms"]):
        # durable_s runs from just before the call to the watcher's stamp
        assert take.t_ended - take.t_call <= durable <= take.t_ended - take.t_call + 0.05
        assert 0.0 <= late_ms <= step_s * 1e3 + 50.0
    # Every take ends 30-70 ms before a step does: read at the step's end, as
    # it was until PR 34, the median would carry that.
    assert 10.0 <= statistics.median(result["durable_seen_late_ms"]) <= 95.0
    assert result["end_to_end"]["save_durable_s"] == statistics.median(result["durable_s"])


@pytest.mark.manifest_shape
@pytest.mark.parametrize("name", MIXES)
def test_the_window_holds_the_saves_that_the_mixs_file_gives(monkeypatch, name):
    """The schedule is by the clock: at a twentieth of the file's times and
    of ``run_seconds``, with steps of 5 ms, the window starts as many saves
    as the file's own numbers give a full run, and follows each to durable."""
    mix = harness.read_json("traffic", f"{name}.json")
    want = saves_a_window(mix, MANIFEST["run_seconds"])
    assert want >= 1
    scaled = {k: float(mix[k]) / 20 for k in ("first_save_s", "save_every_s")}
    loop, result = drive(monkeypatch, scaled, MANIFEST["run_seconds"] / 20, 0.005, [0.05])
    assert result["attempted"] == result["saves_durable"] == want
    assert len(result["stalls_ms"]) == len(result["durable_s"]) == want
    calls = [t.t_call for t in loop.takes]
    gaps = [b - a for a, b in zip(calls, calls[1:])]
    assert all(abs(g - scaled["save_every_s"]) < 0.06 for g in gaps), gaps


@pytest.mark.manifest_shape
def test_the_one_chip_mix_keeps_to_the_rate_that_the_storage_sustains():
    """What PERF.md 6 (PR 34) found on the chip: four saves of 3.65 GB a
    window of 45 s, 0.33 GB/s, drain; eight (every 5.5 s) do not, the
    blobs' fsync falls behind. The interval is also well over twice a
    save's time to durable there (2.4-2.8 s)."""
    mix = harness.read_json("traffic", "save_loop.json")
    assert saves_a_window(mix, MANIFEST["run_seconds"]) == 4
    assert mix["save_every_s"] >= 11.0 >= 2 * 2.8
    assert mix["env"] == {"TPUSNAP_DURABLE_COMMIT": "1"}


def test_a_save_that_outlasts_the_interval_is_waited_for_and_the_wait_is_its_stall(monkeypatch):
    """One take at a time, as a trainer has it: the second take lasts 0.9 s
    of a 0.5 s interval, so the loop waits some 0.4 s before the third save.
    That wait is the second save's stall, once: the third's begins after it.
    The schedule stays by the clock."""
    loop, result = drive(monkeypatch, {"first_save_s": 0.1, "save_every_s": 0.5}, 1.9, 0.01,
                         [0.05, 0.9, 0.05])
    assert result["attempted"] == result["saves_durable"] == 4
    stalls = result["stalls_ms"]
    assert 300.0 <= stalls[1] <= 600.0, stalls
    assert all(abs(s) < 150.0 for k, s in enumerate(stalls) if k != 1), stalls
    assert 0.9 <= result["durable_s"][1] <= 1.0
    # The third save was due 1.1 s in and began when the second was durable,
    # the fourth on its own time again.
    t0 = loop.takes[0].t_call - 0.1
    assert loop.takes[2].t_call - t0 == pytest.approx(1.5, abs=0.1)
    assert loop.takes[3].t_call - t0 == pytest.approx(1.6, abs=0.1)
    # The wait is inside a step of the series, so step_p99_ms and the rate see it.
    assert max(result["series"]["step_ms"]) >= 300.0


def test_a_take_that_fails_is_counted_and_never_stamped(monkeypatch):
    class Failing(SleepingTake):
        def wait(self):
            super().wait()
            raise OSError("the storage went away")

    loop = Loop({"first_save_s": 0.05, "save_every_s": 5.0, "trace_lead_s": 0.1,
                 "trace_max_s": 1.0}, 0.01, [0.1])
    loop.async_take = lambda path, app_state, **kw: Failing(0.1)
    monkeypatch.setattr(save_loop, "Snapshot", loop)
    result = save_loop.run(loop, 0.5)
    assert result["attempted"] == 1 and result["failed"] == 1 and result["saves_durable"] == 0
    assert result["durable_s"] == [] and result["end_to_end"]["save_durable_s"] is None
    assert [label for label, _ in loop.said] == ["save_failed"]


def test_the_rehearsal_overrides_give_a_run_of_a_second(tmp_path):
    """``perf/run.py --rehearsal --seconds 1`` on the one-chip mix: the
    overrides' own schedule, every save followed to durable and stamped no
    later than the loop saw it."""
    cell = next(w for w in MANIFEST["workloads"] if w["traffic"] == "save_loop")
    mix = harness.read_json("traffic", "save_loop.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    proc = subprocess.run(
        [sys.executable, os.path.join(PERF, "run.py"), "--workload", cell["name"], "--seed",
         "3400000007", "--seconds", "1", "--trace", "0", "--rehearsal"],
        capture_output=True, text=True, timeout=400, cwd=ROOT, env=env)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    window = next(json.loads(ln.split(": ", 1)[1]) for ln in proc.stdout.splitlines()
                  if ln.startswith("perf window: "))
    want = saves_a_window({**mix, **mix["rehearsal"]}, 1.0)
    assert window["seconds"] == 1.0 and want >= 2
    assert window["attempted"] == window["saves_durable"] == want and window["failed"] == 0
    assert len(window["durable_seen_late_ms"]) == want
    assert all(late >= 0.0 for late in window["durable_seen_late_ms"])
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["attempted"] == want
