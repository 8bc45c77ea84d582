"""Randomized crash-matrix soak for the two-phase commit protocol.

``test_crash.py`` kills one take mid-write; this matrix SIGKILLs a
take inside each distinct phase of the commit protocol, across seeded
jitter within each window, and asserts the ONE invariant the protocol
promises after every kill (reference invariant: metadata written last,
snapshot invisible until then — torchsnapshot snapshot.py commit
ordering):

    .snapshot_metadata exists  ⟺  the snapshot restores bit-exact
                                   (and scrubs clean)

Windows:
- ``staging``      — inside the staging pass (blob files partial);
- ``residual_io``  — after async_take returned, residual storage I/O
                     still draining in the background thread;
- ``metadata``     — inside the metadata writer, after a PARTIAL
                     temp-file write has been flushed to disk (the
                     temp+rename atomicity window);
- ``durable``      — TPUSNAP_DURABLE_COMMIT=1, inside the pre-barrier
                     durable flush of created dirents.
- ``journal``      — inside the take-journal write, before any blob
                     write (the lifecycle layer's own commit point).

Every window additionally asserts the LIFECYCLE classification
(``tpusnap.lifecycle.fsck_snapshot``): a committed directory fscks as
``committed``; an uncommitted one as ``torn`` (journal present) or
``empty`` — never misclassified as committed. Further down:
SIGKILL-mid-GC, salvage-resume of a torn take (≥50% byte reuse asserted
via the salvaged-bytes counter), and SIGKILL mid-materialize /
mid-retention.

Each (window, seed) run jitters the kill delay within the window, so
kills land at varied instants — including occasionally AFTER the
window completes, which exercises the other side of the ⟺ (metadata
present must imply a perfect restore). The child builds a
deterministic state from the seed so the parent can verify
bit-exactness independently.
"""

import os
import random
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from tpusnap import Snapshot, StateDict, verify_snapshot

_N_ARRAYS = 12
_ARR_SHAPE = (256, 256)  # ~256 KB each -> ~3 MB state, many blobs


def _expected_state(seed: int):
    return {
        f"w{i}": np.random.default_rng(seed * 1000 + i)
        .standard_normal(_ARR_SHAPE)
        .astype(np.float32)
        for i in range(_N_ARRAYS)
    }


_CHILD = r"""
import os, sys, time
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np

window, path, seed = sys.argv[1], sys.argv[2], int(sys.argv[3])

_WINDOW_SLEEP = 1.2

def mark_and_linger():
    # The parent SIGKILLs at a seeded delay within this sleep; if the
    # jitter overshoots, execution proceeds and the take COMPLETES —
    # exercising the "metadata present => restores bit-exact" side.
    print("MARK", flush=True)
    time.sleep(_WINDOW_SLEEP)

import tpusnap.snapshot as snap_mod
import tpusnap.storage_plugins.fs as fs_mod
from tpusnap import Snapshot, StateDict

if window == "staging":
    from tpusnap.io_preparers import array as arr_mod
    orig_stage = arr_mod.ArrayBufferStager._stage_blocking
    fired = [False]
    def hooked(self):
        if not fired[0]:
            fired[0] = True
            mark_and_linger()
        return orig_stage(self)
    arr_mod.ArrayBufferStager._stage_blocking = hooked
elif window == "residual_io":
    # Slow every write so plenty of residual I/O is pending when
    # async_take returns.
    orig_write = fs_mod.FSStoragePlugin.write
    async def slow_write(self, write_io):
        import asyncio
        await asyncio.sleep(0.08)
        await orig_write(self, write_io)
    fs_mod.FSStoragePlugin.write = slow_write
elif window == "metadata":
    orig_meta = snap_mod._write_metadata
    def hooked_meta(storage, metadata, event_loop):
        # A partial, FLUSHED temp write first: the crash window the
        # temp+rename protocol exists for.
        tmp = os.path.join(path, ".snapshot_metadata.crashtmp")
        with open(tmp, "wb") as f:
            f.write(b"{" + b"x" * 100)
            f.flush()
            os.fsync(f.fileno())
        mark_and_linger()
        os.unlink(tmp)
        return orig_meta(storage, metadata, event_loop)
    snap_mod._write_metadata = hooked_meta
elif window == "durable":
    os.environ["TPUSNAP_DURABLE_COMMIT"] = "1"
    # Hook the async method, not the sync shim: the retry middleware
    # wrapper delegates flush_created_dirs() directly.
    orig_flush = fs_mod.FSStoragePlugin.flush_created_dirs
    async def hooked_flush(self):
        mark_and_linger()
        return await orig_flush(self)
    fs_mod.FSStoragePlugin.flush_created_dirs = hooked_flush
elif window == "journal":
    import tpusnap.lifecycle as lc_mod
    orig_journal = lc_mod.write_journal
    def hooked_journal(storage, event_loop, journal):
        mark_and_linger()
        return orig_journal(storage, event_loop, journal)
    lc_mod.write_journal = hooked_journal
else:
    raise SystemExit(f"unknown window {window}")

state = {
    f"w{i}": np.random.default_rng(seed * 1000 + i)
    .standard_normal((256, 256))
    .astype(np.float32)
    for i in range(12)
}
os.environ["TPUSNAP_DISABLE_BATCHING"] = "1"
# Tight heartbeat cadence: the flight recorder's flush rides the pump,
# so this bounds the black-box loss window the parent's timeline
# assertions depend on.
os.environ["TPUSNAP_HEARTBEAT_INTERVAL_S"] = "0.05"

if window == "residual_io":
    pending = Snapshot.async_take(path, {"app": StateDict(**state)})
    mark_and_linger()
    pending.wait()
else:
    Snapshot.take(path, {"app": StateDict(**state)})
print("DONE", flush=True)
"""


def _timeline_json(path: str):
    """In-process ``tpusnap timeline --json`` (spawning a fresh
    interpreter per matrix window would pay a jax import each)."""
    import contextlib
    import io
    import json

    from tpusnap.__main__ import main

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf), contextlib.redirect_stderr(
        io.StringIO()
    ):
        rc = main(["timeline", path, "--json"])
    out = buf.getvalue().strip()
    return rc, (json.loads(out) if out else None)


def _assert_timeline_postmortem(path, window, seed, kill_jitter_s) -> None:
    """Every SIGKILL window: the surviving flight sidecar must let
    ``tpusnap timeline`` name what the killed rank was doing.

    The journal window kills BEFORE the heartbeat pump (and with it the
    flight flusher) starts, so it legitimately has no flight data —
    that exercises the exit-3 leg of the contract instead."""
    rc, doc = _timeline_json(path)
    if window == "journal":
        assert rc in (3, 4), (window, seed, rc)
        return
    assert rc == 4, (window, seed, rc, doc)
    verdict = (doc or {}).get("verdict") or {}
    r0 = (verdict.get("ranks") or {}).get("0")
    assert r0 is not None, (window, seed, doc)
    # The last completed phase is always on record (the pump's first
    # flush lands before any kill window opens).
    assert r0.get("phase") is not None, (window, seed, r0)
    assert r0.get("last_event") is not None, (window, seed, r0)
    if window == "staging" and kill_jitter_s >= 0.15:
        # The kill landed ≥3 flush intervals into the staging sleep, so
        # the last flushed context must name the wedged op and the
        # planned byte denominator.
        assert r0.get("inflight_op") is not None, (window, seed, r0)
        assert (r0.get("bytes_planned") or 0) > 0, (window, seed, r0)


def _run_window(tmp_path, window: str, seed: int, extra_env=None) -> None:
    import select

    path = str(tmp_path / "snap")
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(extra_env or {}))
    proc = subprocess.Popen(
        [sys.executable, "-c", _CHILD, window, path, str(seed)],
        env=env,
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        text=True,
        start_new_session=True,
    )
    try:
        # Wait for the child to enter the window — via select, so a
        # wedged-silent child hits the deadline instead of blocking
        # readline() forever.
        buf = ""
        deadline = time.monotonic() + 120
        marked = eof = False
        while time.monotonic() < deadline and not marked and not eof:
            ready, _, _ = select.select([proc.stdout], [], [], 0.5)
            if not ready:
                continue
            chunk = os.read(proc.stdout.fileno(), 4096).decode(
                "utf-8", errors="replace"
            )
            if chunk == "":
                eof = True
                break
            buf += chunk
            marked = "MARK" in buf
        if not marked:
            os.killpg(proc.pid, signal.SIGKILL)
            proc.wait(timeout=60)
            pytest.fail(
                f"child never reached window {window!r} "
                f"(eof={eof}): {buf[-2000:]}"
            )
        # Seeded jitter: kills land at varied instants inside (and
        # occasionally after) the window.
        kill_jitter_s = random.Random(seed).uniform(0.0, 1.5)
        time.sleep(kill_jitter_s)
        os.killpg(proc.pid, signal.SIGKILL)
        proc.wait(timeout=60)
    finally:
        if proc.poll() is None:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            proc.wait()

    from tpusnap.lifecycle import fsck_snapshot

    meta_path = os.path.join(path, ".snapshot_metadata")
    if os.path.exists(meta_path):
        # Committed ⟹ must be a complete, bit-exact, clean snapshot.
        expected = _expected_state(seed)
        target = {
            "app": StateDict(
                **{k: np.zeros(_ARR_SHAPE, np.float32) for k in expected}
            )
        }
        Snapshot(path).restore(target)
        for k, v in expected.items():
            assert np.array_equal(target["app"][k], v), (window, seed, k)
        assert verify_snapshot(path).clean, (window, seed)
        report = fsck_snapshot(path)
        assert report.state == "committed", (window, seed, report.summary())
        assert not report.missing_referenced, (window, seed)
    else:
        # Not committed ⟹ invisible.
        with pytest.raises(RuntimeError, match="not a snapshot"):
            Snapshot(path).metadata
        # ... and the lifecycle layer classifies the debris: a journal
        # marker makes it torn; pre-journal kills leave empty/foreign.
        report = fsck_snapshot(path)
        if os.path.exists(os.path.join(path, ".tpusnap/journal")):
            assert report.state == "torn", (window, seed, report.summary())
            # The black box survived the SIGKILL: `tpusnap timeline`
            # reconstructs what the killed rank was doing from the
            # flushed flight sidecar.
            _assert_timeline_postmortem(path, window, seed, kill_jitter_s)
        else:
            assert report.state in ("empty", "foreign"), (
                window,
                seed,
                report.summary(),
            )


_WINDOWS = ["staging", "residual_io", "metadata", "durable", "journal"]


@pytest.mark.soak
@pytest.mark.parametrize("window", _WINDOWS)
@pytest.mark.parametrize("seed", range(3))
def test_crash_matrix(tmp_path, window, seed):
    """Fast seeds: run in tier-1 so every commit window stays covered."""
    _run_window(tmp_path, window, seed)


@pytest.mark.soak
@pytest.mark.parametrize("window", ["metadata", "staging"])
def test_crash_matrix_pure_python(tmp_path, window):
    """The pure-Python fallback path (TPUSNAP_DISABLE_NATIVE=1) must keep
    the same crash guarantees — fallback writes have different syscall
    patterns and checksum algorithms, and the metadata self-checksum must
    verify under the fallback CRC too. Fast subset, runs in tier-1."""
    _run_window(tmp_path, window, 0, extra_env={"TPUSNAP_DISABLE_NATIVE": "1"})


@pytest.mark.soak
@pytest.mark.slow
@pytest.mark.parametrize("window", _WINDOWS)
@pytest.mark.parametrize("seed", range(3, 20))
def test_crash_matrix_seed_sweep(tmp_path, window, seed):
    """Wider jitter sweep of the same windows (excluded from tier-1)."""
    _run_window(tmp_path, window, seed)


# --------------------------------------------------- lifecycle windows


def _take_to_completion_or_kill(script: str, args, timeout=150, env=None):
    """Run a child snippet; return (returncode, output)."""
    full_env = dict(os.environ, JAX_PLATFORMS="cpu", **(env or {}))
    proc = subprocess.run(
        [sys.executable, "-c", script, *args],
        env=full_env,
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        text=True,
        timeout=timeout,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    return proc.returncode, proc.stdout


_SALVAGE_CHILD = r"""
import os, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from tpusnap import Snapshot, StateDict

path, seed, crash_at = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
os.environ["TPUSNAP_DISABLE_BATCHING"] = "1"
state = {
    f"w{i}": np.random.default_rng(seed * 1000 + i)
    .standard_normal((256, 256))
    .astype(np.float32)
    for i in range(12)
}
# Deterministic SIGKILL after the Nth successful blob write — the
# chaos layer's registered crash point, no monkeypatching.
Snapshot.take(
    "chaos+fs://" + path,
    {"app": StateDict(**state)},
    storage_options={"fault_plan": {"seed": seed, "crash_after_op": ("write", crash_at)}},
)
print("UNEXPECTED_COMPLETION", flush=True)
"""


@pytest.mark.soak
@pytest.mark.chaos
@pytest.mark.parametrize("seed,crash_at", [(0, 8), (1, 10)])
def test_salvage_resume_of_torn_take(tmp_path, seed, crash_at):
    """SIGKILL a take after N blob writes, then retake the same path with
    the same state: fsck classifies the debris as torn, the retake reuses
    ≥50% of the torn take's intact bytes (asserted via the
    salvaged-bytes counter in the committed rollup), the result restores
    bit-exact and scrubs clean, and a sibling committed snapshot is
    untouched throughout."""
    from tpusnap.knobs import override_batching_disabled
    from tpusnap.lifecycle import fsck_snapshot

    path = str(tmp_path / "snap")
    sibling = str(tmp_path / "sibling")
    expected = _expected_state(seed)
    with override_batching_disabled(True):
        Snapshot.take(sibling, {"app": StateDict(**expected)})

        rc, out = _take_to_completion_or_kill(
            _SALVAGE_CHILD, [path, str(seed), str(crash_at)]
        )
        assert rc == -signal.SIGKILL, (rc, out[-2000:])

        report = fsck_snapshot(path)
        assert report.state == "torn", report.summary()
        # Record flushes coalesce under concurrent writes, so the count
        # can trail the kill point by a few — but never collapse.
        assert report.salvage_records >= crash_at // 2, report.summary()
        assert report.salvage_bytes_present > 0

        # Salvage-retake in this process so the counters are observable
        # both live and in the committed rollup.
        import tpusnap.telemetry as telemetry

        before = telemetry.counter_value("salvage.bytes_salvaged")
        Snapshot.take(path, {"app": StateDict(**expected)})
        salvaged = telemetry.counter_value("salvage.bytes_salvaged") - before
        assert salvaged >= 0.5 * report.salvage_bytes_present, (
            salvaged,
            report.salvage_bytes_present,
        )
        rollup = (Snapshot(path).metadata.extras or {}).get("telemetry", {})
        assert rollup.get("counters", {}).get("salvage.bytes_salvaged", 0) == salvaged

    assert fsck_snapshot(path).state == "committed"
    target = {
        "app": StateDict(**{k: np.zeros_like(v) for k, v in expected.items()})
    }
    Snapshot(path).restore(target)
    for k, v in expected.items():
        assert np.array_equal(target["app"][k], v), k
    assert verify_snapshot(path).clean
    # The sibling committed snapshot was never touched.
    assert fsck_snapshot(sibling).state == "committed"
    assert verify_snapshot(sibling).clean


_PIPELINED_DRAIN_CHILD = r"""
import os, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from tpusnap import Snapshot, StateDict

path, seed, crash_at = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
os.environ["TPUSNAP_DISABLE_BATCHING"] = "1"
# Tight staging window: async_take returns control after ~one blob and
# the deterministic SIGKILL (after the crash_at-th successful blob
# write) lands inside the BACKGROUND drain of the residual windows.
os.environ["TPUSNAP_ASYNC_STAGE_WINDOW_BYTES"] = str(1 << 19)
state = {
    f"w{i}": np.random.default_rng(seed * 1000 + i)
    .standard_normal((256, 256))
    .astype(np.float32)
    for i in range(12)
}
pending = Snapshot.async_take(
    "chaos+fs://" + path,
    {"app": StateDict(**state)},
    storage_options={"fault_plan": {"seed": seed, "crash_after_op": ("write", crash_at)}},
)
print("RETURNED", flush=True)
pending.wait()
print("UNEXPECTED_COMPLETION", flush=True)
"""


@pytest.mark.chaos
@pytest.mark.pipelined
def test_sigkill_in_pipelined_async_drain_is_torn_and_salvageable(tmp_path):
    """SIGKILL inside the background drain of a PIPELINED async take
    (control already returned to "training", residual windows still
    staging/writing): fsck classifies the debris as torn, and a retake
    salvage-resumes the windows the drain had already written instead
    of rewriting them from byte zero."""
    from tpusnap.knobs import override_batching_disabled
    from tpusnap.lifecycle import fsck_snapshot

    seed, crash_at = 3, 6
    path = str(tmp_path / "snap")
    expected = _expected_state(seed)

    rc, out = _take_to_completion_or_kill(
        _PIPELINED_DRAIN_CHILD, [path, str(seed), str(crash_at)]
    )
    assert rc == -signal.SIGKILL, (rc, out[-2000:])
    # The kill landed AFTER control returned (the pipelined contract)
    # and before the commit.
    assert "RETURNED" in out, out[-2000:]
    assert "UNEXPECTED_COMPLETION" not in out, out[-2000:]

    report = fsck_snapshot(path)
    assert report.state == "torn", report.summary()
    assert report.salvage_records >= crash_at // 2, report.summary()
    assert report.salvage_bytes_present > 0

    import tpusnap.telemetry as telemetry

    before = telemetry.counter_value("salvage.bytes_salvaged")
    with override_batching_disabled(True):
        Snapshot.take(path, {"app": StateDict(**expected)})
    salvaged = telemetry.counter_value("salvage.bytes_salvaged") - before
    # The already-written windows were reused, not rewritten.
    assert salvaged >= 0.5 * report.salvage_bytes_present, (
        salvaged,
        report.salvage_bytes_present,
    )
    assert fsck_snapshot(path).state == "committed"
    target = {
        "app": StateDict(**{k: np.zeros_like(v) for k, v in expected.items()})
    }
    Snapshot(path).restore(target)
    for k, v in expected.items():
        assert np.array_equal(target["app"][k], v), k
    assert verify_snapshot(path).clean


_GC_CHILD = r"""
import os, sys, time
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax
jax.config.update("jax_platforms", "cpu")

path = sys.argv[1]
import tpusnap.storage_plugins.fs as fs_mod

orig_delete = fs_mod.FSStoragePlugin.delete
calls = [0]
async def slow_delete(self, p):
    calls[0] += 1
    if calls[0] == 2:
        print("MARK", flush=True)
        time.sleep(1.2)
    await orig_delete(self, p)
fs_mod.FSStoragePlugin.delete = slow_delete

from tpusnap.lifecycle import gc_snapshot
gc_snapshot(path, dry_run=False)
print("DONE", flush=True)
"""


@pytest.mark.soak
def test_crash_mid_gc(tmp_path):
    """SIGKILL inside gc's delete loop: the snapshot stays committed and
    bit-exact, already-deleted orphans stay gone, and a second gc
    reclaims exactly the survivors."""
    import select

    from tpusnap.lifecycle import fsck_snapshot, gc_snapshot

    path = str(tmp_path / "snap")
    expected = _expected_state(0)
    Snapshot.take(path, {"app": StateDict(**expected)})
    orphans = {f"orphan_{i}.blob": 1000 + i for i in range(5)}
    for name, size in orphans.items():
        with open(os.path.join(path, name), "wb") as f:
            f.write(b"x" * size)
    report = fsck_snapshot(path)
    assert set(report.orphans) == set(orphans)

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, "-c", _GC_CHILD, path],
        env=env,
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        text=True,
        start_new_session=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    try:
        buf = ""
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline and "MARK" not in buf:
            ready, _, _ = select.select([proc.stdout], [], [], 0.5)
            if not ready:
                continue
            chunk = os.read(proc.stdout.fileno(), 4096).decode(
                "utf-8", errors="replace"
            )
            if chunk == "":
                break
            buf += chunk
        assert "MARK" in buf, buf[-2000:]
        os.killpg(proc.pid, signal.SIGKILL)
        proc.wait(timeout=60)
    finally:
        if proc.poll() is None:
            os.killpg(proc.pid, signal.SIGKILL)
            proc.wait()

    # Mid-GC crash: committed and clean, some orphans possibly gone.
    report = fsck_snapshot(path)
    assert report.state == "committed", report.summary()
    assert not report.missing_referenced
    remaining = set(report.orphans)
    assert remaining <= set(orphans)
    assert verify_snapshot(path).clean
    # Second gc reclaims exactly the survivors.
    g = gc_snapshot(path, dry_run=False)
    assert set(g.reclaimed) == remaining and not g.errors
    assert not fsck_snapshot(path).orphans
    target = {
        "app": StateDict(**{k: np.zeros_like(v) for k, v in expected.items()})
    }
    Snapshot(path).restore(target)
    for k, v in expected.items():
        assert np.array_equal(target["app"][k], v), k


_MATERIALIZE_CHILD = r"""
import os, sys, time
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax
jax.config.update("jax_platforms", "cpu")

path = sys.argv[1]
import tpusnap.storage_plugins.fs as fs_mod

orig_write = fs_mod.FSStoragePlugin.write
fired = [False]
async def slow_write(self, write_io):
    if not fired[0]:
        fired[0] = True
        print("MARK", flush=True)
        time.sleep(1.2)
    await orig_write(self, write_io)
fs_mod.FSStoragePlugin.write = slow_write

from tpusnap.inspect import materialize_snapshot
materialize_snapshot(path)
print("DONE", flush=True)
"""

_RETAIN_CHILD = r"""
import os, sys, time
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax
jax.config.update("jax_platforms", "cpu")

root = sys.argv[1]
import tpusnap.retention as ret_mod

orig_rmtree = ret_mod.shutil.rmtree
def slow_rmtree(p, *a, **k):
    print("MARK", flush=True)
    time.sleep(1.2)
    return orig_rmtree(p, *a, **k)
ret_mod.shutil.rmtree = slow_rmtree

from tpusnap.retention import apply_retention
apply_retention(root, 2)
print("DONE", flush=True)
"""


def _run_marked_child(script, args, timeout=120):
    """Start a child, wait for MARK, SIGKILL at a short delay."""
    import select

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, "-c", script, *args],
        env=env,
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        text=True,
        start_new_session=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    try:
        buf = ""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline and "MARK" not in buf:
            ready, _, _ = select.select([proc.stdout], [], [], 0.5)
            if not ready:
                continue
            chunk = os.read(proc.stdout.fileno(), 4096).decode(
                "utf-8", errors="replace"
            )
            if chunk == "":
                break
            buf += chunk
        assert "MARK" in buf, buf[-2000:]
        time.sleep(0.3)
        os.killpg(proc.pid, signal.SIGKILL)
        proc.wait(timeout=60)
        return buf
    finally:
        if proc.poll() is None:
            os.killpg(proc.pid, signal.SIGKILL)
            proc.wait()


def _restorable(path, expected):
    target = {
        "app": StateDict(**{k: np.zeros_like(v) for k, v in expected.items()})
    }
    Snapshot(path).restore(target)
    for k, v in expected.items():
        assert np.array_equal(target["app"][k], v), k


@pytest.mark.soak
def test_crash_mid_materialize(tmp_path):
    """SIGKILL inside materialize's blob-copy phase: the increment stays
    committed and still references its (intact) base; the half-copied
    blobs are fsck-visible orphans gc can reclaim; a retried materialize
    completes and cuts the base references."""
    from tpusnap.lifecycle import fsck_snapshot, gc_snapshot

    base = str(tmp_path / "base")
    inc = str(tmp_path / "inc")
    state = _expected_state(1)
    Snapshot.take(base, {"app": StateDict(**state)})
    changed = dict(state, w0=state["w0"] + 1.0)
    Snapshot.take(inc, {"app": StateDict(**changed)}, incremental_from=base)
    assert Snapshot(inc).metadata.base_roots, "increment must reference base"

    _run_marked_child(_MATERIALIZE_CHILD, [inc])

    # Mid-copy crash: both snapshots still committed; the increment
    # still references the base (manifest not rewritten) and restores.
    for p in (base, inc):
        report = fsck_snapshot(p)
        assert report.state == "committed", (p, report.summary())
        assert not report.missing_referenced
    assert Snapshot(inc).metadata.base_roots, "references must survive the crash"
    _restorable(inc, changed)
    # Partially copied blobs are unreferenced orphans; reclaim them.
    gc_snapshot(inc, dry_run=False)
    # Retry completes.
    from tpusnap.inspect import materialize_snapshot

    stats = materialize_snapshot(inc)
    assert stats["blobs_copied"] > 0
    assert Snapshot(inc).metadata.base_roots is None
    _restorable(inc, changed)
    assert verify_snapshot(inc).clean
    assert not fsck_snapshot(inc).missing_referenced


@pytest.mark.soak
def test_crash_mid_retention(tmp_path):
    """SIGKILL between retention's materialize phase and its deletions:
    no kept increment may ever reference a deleted base. After the
    crash every kept snapshot restores; a re-run converges."""
    from tpusnap.lifecycle import fsck_snapshot
    from tpusnap.retention import apply_retention

    root = tmp_path / "snaps"
    root.mkdir()
    s1, s2, s3 = (str(root / f"s{i}") for i in (1, 2, 3))
    state = _expected_state(2)
    Snapshot.take(s1, {"app": StateDict(**state)})
    changed = dict(state, w1=state["w1"] * 2.0)
    Snapshot.take(s2, {"app": StateDict(**changed)}, incremental_from=s1)
    state3 = dict(state, w2=state["w2"] - 3.0)
    Snapshot.take(s3, {"app": StateDict(**state3)})

    _run_marked_child(_RETAIN_CHILD, [str(root)])

    # Whatever the crash point: every surviving committed snapshot must
    # restore — in particular s2, whose base s1 was doomed. Retention
    # materializes BEFORE deleting, so s2 is either still base-backed
    # (s1 present) or already self-contained.
    assert os.path.exists(os.path.join(s2, ".snapshot_metadata"))
    report = fsck_snapshot(s2)
    assert report.state == "committed"
    assert not report.missing_referenced, report.summary()
    if Snapshot(s2).metadata.base_roots:
        assert os.path.exists(os.path.join(s1, ".snapshot_metadata")), (
            "kept increment references a deleted base"
        )
    _restorable(s2, changed)
    _restorable(s3, state3)
    # Re-run converges: 2 snapshots kept, everything restorable.
    apply_retention(str(root), 2)
    assert sorted(os.listdir(root)) == ["s2", "s3"]
    _restorable(s2, changed)
    _restorable(s3, state3)
    assert verify_snapshot(s2).clean and verify_snapshot(s3).clean


# ---------------------------------------------------------------- abort


def _world_abort_mid_take(snap_dir):
    """Rank 1's storage write raises a FATAL error mid-take; rank 0 must
    exit with TakeAbortedError in seconds (not the barrier timeout), no
    ``.snapshot_metadata`` may exist, and the SAME path must be usable
    for a subsequent take."""
    import time as _time

    import numpy as np

    import tpusnap.storage_plugins.fs as fs_mod
    from tpusnap import Snapshot, StateDict, TakeAbortedError, verify_snapshot
    from tpusnap.comm import get_communicator

    comm = get_communicator()
    state = {f"w{i}": np.full((2048,), float(i), np.float32) for i in range(6)}
    orig_write = fs_mod.FSStoragePlugin.write
    if comm.rank == 1:

        async def bad_write(self, write_io):
            raise RuntimeError("injected fatal write")

        fs_mod.FSStoragePlugin.write = bad_write
    t0 = _time.monotonic()
    try:
        Snapshot.take(snap_dir, {"app": StateDict(**state)})
        raise AssertionError("take should have failed")
    except TakeAbortedError:
        dt = _time.monotonic() - t0
        assert comm.rank == 0, "only the peer should see TakeAbortedError"
        assert dt < 30, f"abort propagation took {dt:.1f}s"
        print(f"ABORT_OK {dt:.2f}", flush=True)
    except RuntimeError as e:
        assert comm.rank == 1 and "injected fatal write" in str(e), e
    assert not os.path.exists(os.path.join(snap_dir, ".snapshot_metadata"))
    # The failing rank best-effort deleted its staged blobs; the path is
    # immediately reusable.
    fs_mod.FSStoragePlugin.write = orig_write
    Snapshot.take(snap_dir, {"app": StateDict(**state)})
    if comm.rank == 0:
        assert verify_snapshot(snap_dir).clean
        target = {
            "app": StateDict(
                **{k: np.zeros_like(v) for k, v in state.items()}
            )
        }
        Snapshot(snap_dir).restore(target)
        for k, v in state.items():
            assert np.array_equal(target["app"][k], v), k
        print("REUSE_OK", flush=True)


@pytest.mark.soak
@pytest.mark.distributed
def test_abort_propagates_across_ranks(tmp_path):
    from tpusnap.test_utils import run_subprocess_world

    outs = run_subprocess_world(
        _world_abort_mid_take,
        world_size=2,
        args=[str(tmp_path / "snap")],
        timeout=150,
    )
    assert any("ABORT_OK" in o for o in outs), outs
    assert any("REUSE_OK" in o for o in outs), outs


# ------------------------------------------------ delta-stream windows


_DELTA_CHILD = r"""
import os, sys, time
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["TPUSNAP_DISABLE_BATCHING"] = "1"
os.environ["TPUSNAP_HEARTBEAT_INTERVAL_S"] = "0.05"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np

window, root, seed = sys.argv[1], sys.argv[2], int(sys.argv[3])

_WINDOW_SLEEP = 1.2

def mark_and_linger():
    print("MARK", flush=True)
    time.sleep(_WINDOW_SLEEP)

import tpusnap.storage_plugins.fs as fs_mod
import tpusnap.inspect as inspect_mod
from tpusnap import Snapshot, StateDict

if window == "delta_micro":
    # SIGKILL inside a micro-commit's storage write, after >= 1 delta
    # already committed (so recovery lands on a delta, not the base).
    orig_write = fs_mod.FSStoragePlugin.write
    fired = [False]
    async def hooked(self, write_io):
        root_s = getattr(self, "root", "")
        if (
            not fired[0]
            and "delta-0000" in root_s
            and not root_s.endswith("delta-000001")
            and not write_io.path.startswith(".tpusnap")
        ):
            fired[0] = True
            mark_and_linger()
        await orig_write(self, write_io)
    fs_mod.FSStoragePlugin.write = hooked
elif window == "delta_compact":
    orig_mat = inspect_mod.materialize_snapshot
    def hooked_mat(*a, **kw):
        mark_and_linger()
        return orig_mat(*a, **kw)
    inspect_mod.materialize_snapshot = hooked_mat
elif window != "delta_between":
    raise SystemExit(f"unknown window {window}")

# Self-describing deterministic state: pattern(seed) + step. The
# parent recomputes the expected arrays for ANY committed step k and
# asserts the replayed restore is bit-identical.
pattern = (
    np.random.default_rng(seed).standard_normal((256, 256)).astype(np.float32)
)
state = {"app": StateDict(w=pattern.copy(), step=0)}
max_chain = 2 if window == "delta_compact" else 100
stream = Snapshot.stream(root, state, cadence_s=3600, max_chain=max_chain)
for k in range(1, 8):
    state["app"]["w"] = pattern + np.float32(k)
    state["app"]["step"] = k
    stream.commit_now()
    print(f"COMMIT {stream.seq} {k}", flush=True)
    if window == "delta_between" and k == 3:
        mark_and_linger()
print("DONE", flush=True)
stream.close(final_commit=False)
"""


def _run_delta_window(tmp_path, window: str, seed: int) -> None:
    """SIGKILL a delta stream inside ``window``; assert the chain's
    crash contract: fsck classifies every member, `timeline` names the
    in-flight delta state of a torn tail, and replaying base +
    committed chain restores BIT-IDENTICALLY to the last committed
    micro-commit's reference state (never older than one commit)."""
    import re
    import select

    root = str(tmp_path / "stream")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, "-c", _DELTA_CHILD, window, root, str(seed)],
        env=env,
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        text=True,
        start_new_session=True,
    )
    try:
        buf = ""
        deadline = time.monotonic() + 120
        marked = eof = False
        while time.monotonic() < deadline and not marked and not eof:
            ready, _, _ = select.select([proc.stdout], [], [], 0.5)
            if not ready:
                continue
            chunk = os.read(proc.stdout.fileno(), 4096).decode(
                "utf-8", errors="replace"
            )
            if chunk == "":
                eof = True
                break
            buf += chunk
            marked = "MARK" in buf
        if not marked:
            os.killpg(proc.pid, signal.SIGKILL)
            proc.wait(timeout=60)
            pytest.fail(
                f"child never reached window {window!r} (eof={eof}): "
                f"{buf[-2000:]}"
            )
        kill_jitter_s = random.Random(seed).uniform(0.0, 0.8)
        time.sleep(kill_jitter_s)
        os.killpg(proc.pid, signal.SIGKILL)
        proc.wait(timeout=60)
    finally:
        if proc.poll() is None:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            proc.wait()

    from tpusnap import resolve_chain
    from tpusnap.lifecycle import fsck_snapshot

    # The child printed "COMMIT <seq> <step>" after each completed
    # commit; recovery must land at least there.
    committed_steps = [
        int(m.group(2)) for m in re.finditer(r"COMMIT (\d+) (\d+)", buf)
    ]
    last_printed_step = committed_steps[-1] if committed_steps else 0

    rep = resolve_chain(root)
    assert rep.head is not None, (window, seed, buf[-500:], rep.summary())
    head_path = rep.head_path

    # 1. Replay restore is bit-identical to the last committed
    # micro-commit's reference state (self-describing: step rides the
    # snapshot, so an unprinted trailing commit verifies too).
    pattern = (
        np.random.default_rng(seed)
        .standard_normal((256, 256))
        .astype(np.float32)
    )
    target = {
        "app": StateDict(w=np.zeros((256, 256), np.float32), step=-1)
    }
    Snapshot(head_path).restore(target)
    k = target["app"]["step"]
    assert k >= last_printed_step, (
        f"recovery lost a committed micro-commit: restored step {k} < "
        f"last printed {last_printed_step}"
    )
    expected = pattern + np.float32(k) if k > 0 else pattern
    assert np.array_equal(target["app"]["w"], expected), (window, seed, k)
    assert verify_snapshot(head_path).clean, (window, seed)

    # 2. fsck classification of every member + the torn tail contract.
    head_report = fsck_snapshot(head_path)
    assert head_report.state == "committed", head_report.summary()
    assert head_report.delta is not None, head_report.summary()
    if rep.torn_tail:
        torn_path = os.path.join(root, rep.torn_tail)
        torn_report = fsck_snapshot(torn_path)
        assert torn_report.state == "torn", torn_report.summary()
        assert torn_report.delta is not None, (
            "torn tail lost its chain membership",
            torn_report.summary(),
        )
        assert "torn delta micro-commit" in torn_report.summary()
        # 3. `timeline` names the in-flight delta state (exit 4 =
        # torn-path post-mortem; 3 = killed before the first flight
        # flush, the documented no-data leg).
        rc, doc = _timeline_json(torn_path)
        assert rc in (3, 4), (window, seed, rc)
        if rc == 4:
            assert (doc or {}).get("delta"), doc
            assert doc["delta"].get("seq") is not None, doc
    # Root-level fsck honors the chain exit contract.
    from tpusnap.__main__ import main as _main

    import contextlib
    import io

    with contextlib.redirect_stdout(io.StringIO()):
        rc = _main(["fsck", root])
    assert rc == (4 if rep.torn_tail else 0), (window, seed, rc)


@pytest.mark.soak
@pytest.mark.parametrize(
    "window", ["delta_micro", "delta_between", "delta_compact"]
)
@pytest.mark.parametrize("seed", range(2))
def test_delta_crash_matrix(tmp_path, window, seed):
    """SIGKILL inside a micro-commit, between micro-commits, and
    mid-chain-compaction (tier-1 fast seeds)."""
    _run_delta_window(tmp_path, window, seed)


@pytest.mark.soak
@pytest.mark.slow
@pytest.mark.parametrize(
    "window", ["delta_micro", "delta_between", "delta_compact"]
)
@pytest.mark.parametrize("seed", range(2, 10))
def test_delta_crash_matrix_seed_sweep(tmp_path, window, seed):
    _run_delta_window(tmp_path, window, seed)
