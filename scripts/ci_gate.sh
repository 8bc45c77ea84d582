#!/usr/bin/env bash
# One-entrypoint CI/cron gate for tpusnap:
#
#   1. `tpusnap lint --check` — AST invariant checker over the package
#      (knob access, monotonic clocks, sidecar constants, silent
#      swallows, async blocking calls, finalizer joins, knob/doc
#      drift); runs first because it is the cheapest gate
#   2. tier-1 tests (the ROADMAP.md verify command), run with
#      TPUSNAP_LOCKCHECK=1 by conftest — any lock-order cycle fails
#      the session
#   3. `tpusnap history --check` — cross-run regression gate on this
#      host's history.jsonl: take throughput AND p99 storage-write
#      latency (insufficient history — exit 3 — passes, so a fresh
#      host bootstraps instead of failing forever)
#   4. `tpusnap analyze --check` — performance doctor on the newest
#      bench/CI snapshot (tail latency, stragglers, roofline), when
#      one is available
#   5. `tpusnap slo --check` smoke — checkpoint-SLO gate exit contract:
#      0 on a healthy fresh commit, 2 on a seeded stale-commit breach,
#      3 on an empty telemetry dir (no records)
#   6. delta soak smoke — `Snapshot.stream` against a training loop
#      for ~30 s with TPUSNAP_SLO_RPO_S armed: `tpusnap slo --check`
#      must exit 0 and the measured steady-state RPO (max micro-commit
#      interval) must be ≤ 2x the configured cadence; then a second
#      soak is SIGKILLed inside a micro-commit and the torn tail must
#      honor the chain exit contracts (member fsck exit 4 naming the
#      torn delta micro-commit, root fsck exit 4, timeline exit 4/3)
#   7. `tpusnap timeline` smoke — take → SIGKILL → timeline must honor
#      its exit contract: 0 on a committed path, post-mortem section +
#      exit 4 on a torn one, exit 3 when no flight data exists
#      (matching the trace/analyze zero-span contract)
#   8. write-back tiering smoke — a tiered take against a chaos-wrapped
#      remote commits locally (fsck: local-committed), a drain is
#      killed mid-upload (SIGKILL), the resumed `tpusnap drain`
#      converges to remote-durable skipping journal-proven blobs, and
#      the `fsck`/`drain` exit contracts hold at each state; hermetic
#      like the timeline/slo smokes
#   9. fused-compression smoke — a forced-compressed take must scrub
#      clean and restore bit-exact, the auto policy must bypass against
#      a pinned-fast pipe ceiling (codec-free manifest; pinned so the
#      gate tests the policy, not this runner's disk weather) and
#      choose compress against the chaos token-bucket throttle, and the
#      throttled compressed snapshot must restore bit-exact; hermetic
#      like the timeline/slo/tiering smokes (SIGKILL-mid-compressed-
#      take salvage lives in tier-1: tests/test_compress.py; the
#      measured local-disk bypass claim lives in bench.py)
#  10. rank-failure smoke — a 2-process take whose rank 1 is SIGKILLed
#      by a rank-scoped chaos plan (`rank=1,crash_after_op=write:1`)
#      must fail on the survivor with RankFailedError naming the dead
#      rank within seconds (lease liveness, not the 600 s barrier
#      timeout); a second 2-process fully-replicated take under
#      TPUSNAP_RANK_FAILURE=degrade must COMMIT on the survivor, scrub
#      clean, restore bit-exact, and record the adoption in
#      extras["degraded"]; hermetic like the other smokes
#  11. elastic-stream smoke — the ISSUE 16 acceptance scenarios as a
#      gate: a 2-process `Snapshot.stream` whose rank 1 is SIGKILLed
#      mid-micro-commit must keep streaming via a degraded epoch
#      (fsck-clean chain, bit-exact restore), and a graceful
#      `leave()` + later re-join must re-plan the epoch world with
#      the joins/leaves recorded in the per-epoch chain metadata
#  12. mini-fleetsim smoke — 3 concurrent jobs (one SIGKILLed by a
#      rank-kill fault, one writing through a seeded outage window)
#      publishing into one shared TPUSNAP_FLEET_DIR; `tpusnap fleet
#      --check` must honor its full exit contract: 3 on the empty
#      fleet dir, 0 across the live fleet under generous thresholds,
#      2 against a seeded stale (non-final, old-commit) job record
#  13. CAS smoke — two sequential jobs take identical content through
#      one shared content-addressed store (TPUSNAP_CAS_DIR): the blobs
#      dedup to one job's worth, a gc sweep is SIGKILLed mid-delete by
#      a chaos plan on the store URL, the re-run gc steals the dead
#      sweeper's lease and converges, and `fsck --store` exits 0 with
#      the surviving job's refs intact
#  14. OPTIONAL real-backend cloud suite — when a `fake-gcs-server`
#      and/or `minio` binary is on PATH, run the `cloud_real` pytest
#      marker against the real server processes (skipped silently
#      when the binaries are absent)
#  15. tune smoke — `tpusnap tune` exit contract: 3 against an empty
#      history (insufficient comparable events), 0 with a plan against
#      a seeded history; then a TPUSNAP_AUTOTUNE=1 restore must stamp
#      the applied plan (`tuned: {plan_id, knobs}`) into its history
#      event; hermetic like the other smokes
#  16. access-ledger heatmap smoke — `tpusnap heatmap` exit contract:
#      3 with no reader ledgers, 0 after a partial read_object (with
#      coverage < 100% naming only the read leaf), and 2 under --check
#      when a 3-reader cohort's merged amplification crosses the
#      --max-amplification gate; hermetic like the other smokes
#
# Usage:
#   scripts/ci_gate.sh [SNAPSHOT_PATH]
#
#   SNAPSHOT_PATH        snapshot for step 4 (default: $TPUSNAP_CI_SNAPSHOT,
#                        else step 4 is skipped with a note)
#   TPUSNAP_CI_SKIP_TESTS=1   skip step 2 (cron boxes that only gate
#                             perf trends, not code)
#
# Exit: non-zero on the first failing gate, echoing which one.

set -u -o pipefail

cd "$(dirname "$0")/.."

fail() { echo "ci_gate: FAIL — $1" >&2; exit "$2"; }

# ---- 1. static analysis --------------------------------------------------
echo "ci_gate: [1/16] lint --check (AST invariants)"
env JAX_PLATFORMS=cpu python -m tpusnap lint --check
rc=$?
[ "$rc" -eq 0 ] || fail "tpusnap lint --check (rc=$rc)" "$rc"

# ---- 2. tier-1 -----------------------------------------------------------
if [ "${TPUSNAP_CI_SKIP_TESTS:-0}" != "1" ]; then
    echo "ci_gate: [2/16] tier-1 tests"
    rm -f /tmp/_t1.log
    # cloud_real excluded here: on a host with the server binaries the
    # real-backend suite belongs to step 8, not inside the fast tier.
    timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
        -m 'not slow and not cloud_real' --continue-on-collection-errors \
        -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log
    rc=${PIPESTATUS[0]}
    echo "DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)"
    [ "$rc" -eq 0 ] || fail "tier-1 tests (rc=$rc)" "$rc"
else
    echo "ci_gate: [2/16] tier-1 tests skipped (TPUSNAP_CI_SKIP_TESTS=1)"
fi

# ---- 3. cross-run history gate ------------------------------------------
echo "ci_gate: [3/16] history --check (throughput + p99 write latency + restore read roofline)"
for kind in take bench; do
    python -m tpusnap history --check --kind "$kind" \
        --metric throughput_gbps --metric storage_write_p99_s --json
    rc=$?
    case "$rc" in
        0) echo "ci_gate: history[$kind] OK" ;;
        3) echo "ci_gate: history[$kind] insufficient comparable history (bootstrapping) — pass" ;;
        *) fail "history --check --kind $kind regressed (rc=$rc)" "$rc" ;;
    esac
done
# Restore lane: restore_roofline_fraction has no _s suffix, so the gate
# treats it higher-is-better — a read-path efficiency slide (fraction
# falling against its baseline) trips CI even when wall-clock hides it.
python -m tpusnap history --check --kind restore \
    --metric restore_roofline_fraction --metric storage_read_p99_s --json
rc=$?
case "$rc" in
    0) echo "ci_gate: history[restore] OK" ;;
    3) echo "ci_gate: history[restore] insufficient comparable history (bootstrapping) — pass" ;;
    *) fail "history --check --kind restore regressed (rc=$rc)" "$rc" ;;
esac

# ---- 4. analyze doctor on the latest snapshot ---------------------------
SNAP="${1:-${TPUSNAP_CI_SNAPSHOT:-}}"
if [ -n "$SNAP" ]; then
    echo "ci_gate: [4/16] analyze --check $SNAP"
    python -m tpusnap analyze --check --history --min-read-roofline 0.4 "$SNAP"
    rc=$?
    case "$rc" in
        0) echo "ci_gate: analyze OK" ;;
        3) echo "ci_gate: analyze found no telemetry in $SNAP — pass (knob-off take)" ;;
        *) fail "analyze --check $SNAP (rc=$rc)" "$rc" ;;
    esac
else
    echo "ci_gate: [4/16] analyze skipped (no snapshot; pass a path or set TPUSNAP_CI_SNAPSHOT)"
fi

# ---- 5. checkpoint-SLO gate smoke ---------------------------------------
echo "ci_gate: [5/16] slo --check smoke (exit contract: 0 healthy / 2 breach / 3 no records)"
env JAX_PLATFORMS=cpu python - <<'PYEOF'
import json, os, shutil, subprocess, sys, tempfile, time

work = tempfile.mkdtemp(prefix="tpusnap_ci_slo_")
tele = os.path.join(work, "tele")
# Hermetic like the timeline smoke: the takes here must not feed the
# HOST history this gate's own step 3 grades.
env = dict(os.environ, JAX_PLATFORMS="cpu",
           TPUSNAP_TELEMETRY_DIR=tele, TPUSNAP_HISTORY="0")
import atexit
atexit.register(shutil.rmtree, work, True)

def slo(*extra, tdir=tele):
    e = dict(env, TPUSNAP_TELEMETRY_DIR=tdir)
    return subprocess.run(
        [sys.executable, "-m", "tpusnap", "slo", "--check", *extra],
        capture_output=True, text=True, env=e, timeout=120,
    )

def die(msg):
    print(f"slo smoke: FAIL - {msg}", file=sys.stderr)
    sys.exit(1)

# (a) empty telemetry dir -> exit 3
r = slo(tdir=os.path.join(work, "empty"))
if r.returncode != 3:
    die(f"empty dir: expected exit 3, got {r.returncode}: {r.stderr[-300:]}")

# (b) committed take -> healthy under a generous RPO threshold -> exit 0
take = (
    "import os; os.environ.setdefault('JAX_PLATFORMS','cpu');\n"
    "import jax; jax.config.update('jax_platforms','cpu');\n"
    "import numpy as np, sys\n"
    "from tpusnap import Snapshot, StateDict\n"
    "Snapshot.take(sys.argv[1], {'a': StateDict(w=np.arange(200000, dtype=np.float32))})\n"
)
subprocess.run([sys.executable, "-c", take, os.path.join(work, "snap")],
               check=True, env=env, timeout=180)
r = slo("--rpo", "3600")
if r.returncode != 0:
    die(f"healthy: expected exit 0, got {r.returncode}: {r.stdout[-300:]}{r.stderr[-300:]}")

# (c) seeded stale commit -> breach -> exit 2
rec_path = os.path.join(tele, "slo", "rank_0.json")
rec = json.load(open(rec_path))
rec["last_commit_ts"] = time.time() - 900  # 15 minutes stale
json.dump(rec, open(rec_path, "w"))
r = slo("--rpo", "60")
if r.returncode != 2:
    die(f"stale breach: expected exit 2, got {r.returncode}: {r.stdout[-300:]}")
print("slo smoke: OK (3/3 contract legs)")
PYEOF
rc=$?
[ "$rc" -eq 0 ] || fail "slo --check smoke (rc=$rc)" "$rc"

# ---- 6. delta soak smoke -------------------------------------------------
echo "ci_gate: [6/16] delta soak smoke (stream ~30s: slo --check green, RPO <= 2x cadence; SIGKILL -> torn-tail contracts)"
env JAX_PLATFORMS=cpu python - <<'PYEOF'
import json, os, re, shutil, signal, subprocess, sys, tempfile, time

work = tempfile.mkdtemp(prefix="tpusnap_ci_delta_")
tele = os.path.join(work, "tele")
# Hermetic observability (see the slo/timeline smokes) + the RPO
# objective ARMED for the whole soak: a healthy stream must never
# breach it, and `slo --check` reads the same env threshold.
env = dict(os.environ, JAX_PLATFORMS="cpu",
           TPUSNAP_TELEMETRY_DIR=tele, TPUSNAP_HISTORY="0",
           TPUSNAP_SLO_RPO_S="10",
           TPUSNAP_HEARTBEAT_INTERVAL_S="0.05")
import atexit
atexit.register(shutil.rmtree, work, True)

def die(msg):
    print(f"delta soak: FAIL - {msg}", file=sys.stderr)
    sys.exit(1)

CADENCE = 1.0
_SOAK = r"""
import json, os, sys, time
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from tpusnap import Snapshot, StateDict

root, duration, cadence, kill_mode = (
    sys.argv[1], float(sys.argv[2]), float(sys.argv[3]), sys.argv[4]
)
if kill_mode == "kill":
    # Make the torn window deterministic: the first payload write into
    # a delta member past seq 1 announces itself and lingers, so the
    # parent's SIGKILL always lands inside a micro-commit.
    import tpusnap.storage_plugins.fs as fs_mod
    orig_write = fs_mod.FSStoragePlugin.write
    fired = [False]
    async def hooked(self, write_io):
        root_s = getattr(self, "root", "")
        if (not fired[0] and "delta-0000" in root_s
                and not root_s.endswith("delta-000001")
                and not write_io.path.startswith(".tpusnap")):
            fired[0] = True
            print("MARK", flush=True)
            time.sleep(2.0)
        await orig_write(self, write_io)
    fs_mod.FSStoragePlugin.write = hooked

state = {"m": StateDict(w=np.zeros((512, 512), np.float32), step=0)}
stream = Snapshot.stream(root, state, cadence_s=cadence)
t0, k = time.monotonic(), 0
while time.monotonic() - t0 < duration:
    k += 1
    state["m"]["w"][k % 512, :] = float(k)
    state["m"]["step"] = k
    stream.mark_step(bytes_changed=2048)
    time.sleep(0.01)
stream.close()
stream.raise_if_failed()
print("STATS " + json.dumps(stream.stats), flush=True)
"""

# (a) healthy ~30 s soak: clean close, slo --check green, measured
# steady-state RPO (max micro-commit interval) <= 2x cadence.
root = os.path.join(work, "stream")
r = subprocess.run(
    [sys.executable, "-c", _SOAK, root, "30", str(CADENCE), "run"],
    capture_output=True, text=True, env=env, timeout=240,
)
if r.returncode != 0:
    die(f"soak child failed rc={r.returncode}: {r.stdout[-400:]}{r.stderr[-400:]}")
m = re.search(r"STATS (\{.*\})", r.stdout)
if not m:
    die(f"soak printed no stats: {r.stdout[-400:]}")
stats = json.loads(m.group(1))
if stats["commits"] < 3:
    die(f"soak produced only {stats['commits']} micro-commit(s)")
rpo = stats.get("max_commit_interval_s")
if rpo is None or rpo > 2 * CADENCE:
    die(f"measured RPO {rpo}s exceeds 2x cadence ({2 * CADENCE}s)")
r = subprocess.run(
    [sys.executable, "-m", "tpusnap", "slo", "--check"],
    capture_output=True, text=True, env=env, timeout=120,
)
if r.returncode != 0:
    die(f"slo --check after soak: expected 0, got {r.returncode}: "
        f"{r.stdout[-300:]}")
print(f"delta soak: healthy leg OK ({stats['commits']} commits, "
      f"max interval {rpo}s <= {2 * CADENCE}s, slo --check green)")

# (b) SIGKILL inside a micro-commit -> torn-tail exit contracts.
root2 = os.path.join(work, "stream_kill")
proc = subprocess.Popen(
    [sys.executable, "-c", _SOAK, root2, "60", "0.4", "kill"],
    env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    start_new_session=True,
)
buf, deadline = "", time.monotonic() + 120
while time.monotonic() < deadline and "MARK" not in buf:
    line = proc.stdout.readline()
    if line == "":
        break
    buf += line
if "MARK" not in buf:
    os.killpg(proc.pid, signal.SIGKILL); proc.wait(timeout=60)
    die(f"kill soak never reached the write window: {buf[-400:]}")
time.sleep(0.3)
os.killpg(proc.pid, signal.SIGKILL)
proc.wait(timeout=60)

def cli(*args):
    return subprocess.run(
        [sys.executable, "-m", "tpusnap", *args],
        capture_output=True, text=True, env=env, timeout=120,
    )

torn = sorted(
    d for d in os.listdir(root2)
    if d.startswith("delta-")
    and not os.path.exists(os.path.join(root2, d, ".snapshot_metadata"))
)
if not torn:
    die(f"SIGKILL left no torn member under {root2}: {os.listdir(root2)}")
member = os.path.join(root2, torn[-1])
r = cli("fsck", member)
if r.returncode != 4:
    die(f"member fsck: expected 4 (torn), got {r.returncode}: {r.stdout[-300:]}")
if "torn delta micro-commit" not in r.stdout:
    die(f"member fsck does not name the torn delta state: {r.stdout[-300:]}")
r = cli("fsck", root2)
if r.returncode != 4:
    die(f"root fsck: expected 4 (torn tail), got {r.returncode}: {r.stdout[-300:]}")
r = cli("timeline", member)
if r.returncode not in (3, 4):
    die(f"timeline on torn member: expected 4 (or 3 pre-flush), got "
        f"{r.returncode}: {r.stderr[-300:]}")
print("delta soak: OK (healthy RPO leg + torn-tail contract leg)")
PYEOF
rc=$?
[ "$rc" -eq 0 ] || fail "delta soak smoke (rc=$rc)" "$rc"

# ---- 7. flight-recorder timeline smoke ----------------------------------
echo "ci_gate: [7/16] timeline smoke (exit contract: 0 committed / 4 torn / 3 no data)"
env JAX_PLATFORMS=cpu python - <<'PYEOF'
import os, shutil, signal, subprocess, sys, tempfile

work = tempfile.mkdtemp(prefix="tpusnap_ci_timeline_")
# Hermetic observability: the smoke's takes must not append kind=take
# events to the HOST history this gate's own step 3 grades, nor leak
# flight-copy dirs under the real telemetry dir — scope both to the
# workdir that is removed at exit.
env = dict(os.environ, JAX_PLATFORMS="cpu",
           TPUSNAP_TELEMETRY_DIR=os.path.join(work, "tele"),
           TPUSNAP_HISTORY="0")
# Cron boxes run this forever: the snapshots made here must not
# accumulate under /tmp.
import atexit
atexit.register(shutil.rmtree, work, True)

def timeline(path, *extra):
    return subprocess.run(
        [sys.executable, "-m", "tpusnap", "timeline", path, *extra],
        capture_output=True, text=True, env=env, timeout=180,
    )

def die(msg):
    print(f"timeline smoke: FAIL - {msg}", file=sys.stderr)
    sys.exit(1)

# (a) no flight data -> exit 3
empty = os.path.join(work, "empty"); os.makedirs(empty)
r = timeline(empty)
if r.returncode != 3:
    die(f"empty dir: expected exit 3, got {r.returncode}: {r.stderr[-300:]}")

# (b) committed take -> exit 0
committed = os.path.join(work, "committed")
take = (
    "import os; os.environ.setdefault('JAX_PLATFORMS','cpu');\n"
    "import jax; jax.config.update('jax_platforms','cpu');\n"
    "import numpy as np, sys\n"
    "from tpusnap import Snapshot, StateDict\n"
    "Snapshot.take(sys.argv[1], {'a': StateDict(w=np.arange(200000, dtype=np.float32))})\n"
)
subprocess.run([sys.executable, "-c", take, committed], check=True, env=env, timeout=180)
r = timeline(committed)
if r.returncode != 0:
    die(f"committed: expected exit 0, got {r.returncode}: {r.stderr[-300:]}")

# (c) SIGKILL mid-take -> torn, post-mortem section, exit 4
torn = os.path.join(work, "torn")
kill = (
    "import os, sys; os.environ.setdefault('JAX_PLATFORMS','cpu');\n"
    "os.environ['TPUSNAP_DISABLE_BATCHING']='1';\n"
    "os.environ['TPUSNAP_HEARTBEAT_INTERVAL_S']='0.05';\n"
    "os.environ['TPUSNAP_FAULT_SPEC']='latency_ms=300,crash_after_op=write:4';\n"
    "import jax; jax.config.update('jax_platforms','cpu');\n"
    "import numpy as np\n"
    "from tpusnap import Snapshot, StateDict\n"
    "state={f'w{i}': np.random.default_rng(i).standard_normal((128,128)).astype(np.float32) for i in range(8)}\n"
    "Snapshot.take('chaos+fs://'+sys.argv[1], {'a': StateDict(**state)})\n"
)
r = subprocess.run([sys.executable, "-c", kill, torn], capture_output=True, text=True, env=env, timeout=180)
if r.returncode != -signal.SIGKILL:
    die(f"kill child: expected SIGKILL, got {r.returncode}: {r.stdout[-300:]}")
r = timeline(torn)
if r.returncode != 4:
    die(f"torn: expected exit 4, got {r.returncode}: {r.stderr[-300:]}")
if "POST-MORTEM" not in r.stdout:
    die("torn: post-mortem section missing from output")
print("timeline smoke: OK (3/3 contract legs)")
PYEOF
rc=$?
[ "$rc" -eq 0 ] || fail "timeline smoke (rc=$rc)" "$rc"

# ---- 8. write-back tiering smoke ----------------------------------------
echo "ci_gate: [8/16] tiering smoke (local commit -> SIGKILL mid-drain -> resumed drain -> remote-durable)"
env JAX_PLATFORMS=cpu python - <<'PYEOF'
import json, os, shutil, signal, subprocess, sys, tempfile

work = tempfile.mkdtemp(prefix="tpusnap_ci_tier_")
# Hermetic observability: tier status + history scoped to the workdir.
env = dict(os.environ, JAX_PLATFORMS="cpu",
           TPUSNAP_TELEMETRY_DIR=os.path.join(work, "tele"),
           TPUSNAP_HISTORY="0", TPUSNAP_TIER_DRAIN="0")
import atexit
atexit.register(shutil.rmtree, work, True)

def die(msg):
    print(f"tiering smoke: FAIL - {msg}", file=sys.stderr)
    sys.exit(1)

def cli(*args, **kw):
    return subprocess.run(
        [sys.executable, "-m", "tpusnap", *args],
        capture_output=True, text=True, env=dict(env, **kw), timeout=180,
    )

cache = os.path.join(work, "cache")
remote = os.path.join(work, "remote")
url = f"tier+local={cache}+remote=fs://{remote}/snap"
local_dir = os.path.join(cache, remote.lstrip("/"), "snap")

# (a) tiered take (chaos-wrapped remote scheme would not matter here:
# the take never touches the remote) -> fsck committed + local-committed,
# drain --status exit 2 (tiered, not yet durable).
take = (
    "import os, sys; os.environ.setdefault('JAX_PLATFORMS','cpu')\n"
    "os.environ['TPUSNAP_DISABLE_BATCHING']='1'\n"
    "import jax; jax.config.update('jax_platforms','cpu')\n"
    "import numpy as np\n"
    "from tpusnap import Snapshot, StateDict\n"
    "state={f'w{i}': np.random.default_rng(i).standard_normal((128,128)).astype(np.float32) for i in range(6)}\n"
    "Snapshot.take(sys.argv[1], {'a': StateDict(**state)})\n"
)
subprocess.run([sys.executable, "-c", take, url], check=True, env=env, timeout=180)
r = cli("fsck", local_dir)
if r.returncode != 0 or "local-committed" not in r.stdout:
    die(f"post-take fsck: rc={r.returncode}: {r.stdout[-300:]}")
r = cli("drain", local_dir, "--status")
if r.returncode != 2:
    die(f"drain --status pre-drain: expected 2, got {r.returncode}")

# (b) kill the uploader mid-drain (chaos remote SIGKILLs after the 3rd
# successful upload), then the resumed drain must reach remote-durable
# re-uploading nothing already journal-proven.
kill_drain = (
    "import os, sys; os.environ.setdefault('JAX_PLATFORMS','cpu')\n"
    "os.environ['TPUSNAP_FAULT_SPEC']='crash_after_op=write:3'\n"
    "import jax; jax.config.update('jax_platforms','cpu')\n"
    "from tpusnap import tiering\n"
    "spec = tiering.parse_tier_url(sys.argv[1])\n"
    "tiering.drain_snapshot(sys.argv[1], remote_url='chaos+'+spec.remote_url)\n"
)
r = subprocess.run([sys.executable, "-c", kill_drain, url],
                   capture_output=True, text=True, env=env, timeout=180)
if r.returncode != -signal.SIGKILL:
    die(f"kill drain: expected SIGKILL, got {r.returncode}: {r.stdout[-300:]}{r.stderr[-300:]}")
r = cli("fsck", local_dir)
if r.returncode != 0 or "local-committed" not in r.stdout:
    die(f"post-kill fsck must stay local-committed: {r.stdout[-300:]}")

r = cli("drain", url, "--json")
if r.returncode != 0:
    die(f"resumed drain: expected 0, got {r.returncode}: {r.stdout[-300:]}{r.stderr[-300:]}")
rep = json.loads(r.stdout)
if rep["state"] != "durable" or rep["blobs_skipped"] < 2:
    die(f"resumed drain did not skip journal-proven blobs: {rep}")

# (c) exit contracts at the durable state + the remote restores.
r = cli("fsck", local_dir)
if r.returncode != 0 or "remote-durable" not in r.stdout:
    die(f"post-drain fsck: {r.stdout[-300:]}")
r = cli("drain", local_dir, "--status")
if r.returncode != 0:
    die(f"drain --status post-drain: expected 0, got {r.returncode}")
r = cli("fsck", os.path.join(remote, "snap"))
if r.returncode != 0:
    die(f"remote fsck: expected 0 (committed), got {r.returncode}: {r.stdout[-300:]}")
print(f"tiering smoke: OK (take local, SIGKILL mid-drain, resume skipped "
      f"{rep['blobs_skipped']}/{rep['blobs_skipped']+rep['blobs_uploaded']} blobs, remote-durable)")
PYEOF
rc=$?
[ "$rc" -eq 0 ] || fail "tiering smoke (rc=$rc)" "$rc"

# ---- 9. fused-compression smoke ------------------------------------------
echo "ci_gate: [9/16] compression smoke (compressed take -> fsck/scrub clean -> bit-exact restore; auto bypasses locally, compresses on a throttled pipe)"
env JAX_PLATFORMS=cpu python - <<'PYEOF'
import os, shutil, sys, tempfile

work = tempfile.mkdtemp(prefix="tpusnap_ci_compress_")
# Hermetic observability, same contract as the slo/timeline/tiering
# smokes: nothing here feeds the HOST history step 3 grades.
os.environ.update(JAX_PLATFORMS="cpu",
                  TPUSNAP_TELEMETRY_DIR=os.path.join(work, "tele"),
                  TPUSNAP_HISTORY="0")
import atexit
atexit.register(shutil.rmtree, work, True)

import numpy as np

from tpusnap import Snapshot, StateDict, compress, verify_snapshot
from tpusnap.knobs import override_compress


def die(msg):
    print(f"compression smoke: FAIL - {msg}", file=sys.stderr)
    sys.exit(1)


if not __import__("tpusnap")._native.compression_available():
    print("compression smoke: SKIP (native codec unavailable)")
    sys.exit(0)

# bf16-precision f32 (mantissa-truncated random): the shape the shuffle
# filter targets, with real entropy in the exponent planes.
rng = np.random.default_rng(0xC0)
a = rng.standard_normal((96 << 20) // 4).astype(np.float32)
a = (a.view(np.uint32) & np.uint32(0xFFFF0000)).view(np.float32)

# (a) forced-compressed take -> codec recorded, stored < logical,
# scrub clean, bit-exact restore.
on_path = os.path.join(work, "on", "snap")
with override_compress(mode="on", min_blob_bytes=1 << 20):
    Snapshot.take(on_path, {"app": StateDict(w=a)})
entry = Snapshot(on_path).metadata.manifest["0/app/w"]
if not entry.codec:
    die("forced take recorded no codec on the manifest entry")
stored = sum(
    os.path.getsize(os.path.join(r, f))
    for r, _, fs in os.walk(on_path)
    for f in fs
    if not f.endswith(".snapshot_metadata")
)
if stored >= a.nbytes:
    die(f"compressed take stored {stored} >= logical {a.nbytes}")
rep = verify_snapshot(on_path)
if not rep.clean or rep.corrupt:
    die(f"scrub of compressed snapshot not clean: {rep}")
tgt = {"app": StateDict(w=np.zeros_like(a))}
Snapshot(on_path).restore(tgt)
if not np.array_equal(tgt["app"]["w"], a):
    die("compressed restore is not bit-exact")

# (b) auto policy against a PINNED fast pipe: seed the ceiling
# registry with a known-fast sample for this backend label, so the
# gate asserts the policy's decision logic, not this runner's disk
# weather (a cgroup-throttled CI disk slower than what the codec
# takes off it would legitimately compress — bench.py owns the
# measured-local claim). Manifest stays codec-free on a bypassed take.
from tpusnap.storage_plugin import url_to_storage_plugin

compress._reset_ceilings()
auto_path = os.path.join(work, "auto", "snap")
_probe_plugin = url_to_storage_plugin(auto_path)
compress.note_pipe_ceiling(compress.pipe_ceiling_key(_probe_plugin), 100.0)
with override_compress(mode="auto"):
    Snapshot.take(auto_path, {"app": StateDict(w=a)})
dec = compress.LAST_DECISION
if dec is None or dec.compress:
    die(f"auto against a pinned-fast pipe must bypass, got {dec}")
if dec.reason != "pipe_outruns_codec":
    die(f"auto bypass drew the wrong reason: {dec}")
if Snapshot(auto_path).metadata.manifest["0/app/w"].codec:
    die("auto-bypassed take recorded a codec")

# (c) auto policy against a bandwidth-throttled pipe (chaos token
# bucket at 0.05 GB/s, far under this host's measured codec rate):
# must compress, and the throttled snapshot still restores bit-exact.
compress._reset_ceilings()
thr_path = os.path.join(work, "thr", "snap")
with override_compress(mode="auto"):
    Snapshot.take(
        f"chaos+file://{thr_path}",
        {"app": StateDict(w=a)},
        storage_options={
            "fault_plan": "transient_per_op=0,bandwidth_gbps=0.05"
        },
    )
dec = compress.LAST_DECISION
if dec is None or not dec.compress:
    die(f"auto on a 0.05 GB/s pipe must compress, got {dec}")
tgt = {"app": StateDict(w=np.zeros_like(a))}
Snapshot(thr_path).restore(tgt)
if not np.array_equal(tgt["app"]["w"], a):
    die("throttled compressed restore is not bit-exact")

print(
    "compression smoke: OK (forced take scrub-clean + bit-exact, "
    f"ratio {a.nbytes / stored:.2f}x; auto bypassed the pinned-fast "
    f"pipe and compressed on the throttled one)"
)
PYEOF
rc=$?
[ "$rc" -eq 0 ] || fail "compression smoke (rc=$rc)" "$rc"

# ---- 10. rank-failure smoke ----------------------------------------------
echo "ci_gate: [10/16] rank-failure smoke (chaos rank-kill -> fast RankFailedError; degrade-mode replicated take -> committed + scrub clean)"
env JAX_PLATFORMS=cpu python - <<'PYEOF'
import atexit, os, re, shutil, subprocess, sys, tempfile

work = tempfile.mkdtemp(prefix="tpusnap_ci_rankfail_")
atexit.register(shutil.rmtree, work, True)

def die(msg):
    print(f"rank-failure smoke: FAIL - {msg}", file=sys.stderr)
    sys.exit(1)

# The world script re-imported by run_subprocess_world's rank children
# must live in an importable file (a heredoc has no module path).
WORLD = r'''
import os, signal, sys, time

import numpy as np


def _arrays(seed=5, n=4):
    rng = np.random.default_rng(seed)
    return {
        f"w{i}": rng.standard_normal(16384).astype(np.float32)
        for i in range(n)
    }


def world_fast_abort(snap_dir):
    # Leg (a): TPUSNAP_FAULT_SPEC="rank=1,...,crash_after_op=write:1"
    # SIGKILLs exactly rank 1 after its first chaos blob write; rank 0
    # must fail fast with RankFailedError naming it — seconds, not the
    # 600 s barrier timeout.
    from tpusnap import RankFailedError, Snapshot, StateDict

    state = {"m": StateDict(**_arrays())}
    t0 = time.monotonic()
    try:
        Snapshot.take("chaos+fs://" + snap_dir, state, replicated=["**"])
    except RankFailedError as e:
        dt = time.monotonic() - t0
        assert e.ranks == [1], e.ranks
        assert dt <= 15.0, f"detection took {dt:.1f}s"
        print(f"RANKFAILED dt={dt:.2f}", flush=True)
        os._exit(0)  # skip the shutdown rendezvous with the dead peer
    raise AssertionError("rank 0 never observed the rank failure")


def world_degraded(snap_dir):
    # Leg (b): TPUSNAP_RANK_FAILURE=degrade + a fully-replicated state:
    # rank 1 dies mid-write, rank 0 completes the take, scrubs it
    # clean, and the metadata records the adoption.
    from tpusnap import Snapshot, StateDict, verify_snapshot
    from tpusnap.comm import get_communicator

    comm = get_communicator()
    arrays = _arrays(seed=9)
    if comm.rank == 1:
        import tpusnap.storage_plugins.fs as fs_mod

        orig = fs_mod.FSStoragePlugin.write
        fired = [0]

        async def hooked(self, write_io):
            await orig(self, write_io)
            if not write_io.path.startswith(".tpusnap"):
                fired[0] += 1
                if fired[0] == 1:
                    os.kill(os.getpid(), signal.SIGKILL)

        fs_mod.FSStoragePlugin.write = hooked
    snap = Snapshot.take(snap_dir, {"m": StateDict(**arrays)}, replicated=["**"])
    deg = (snap.metadata.extras or {}).get("degraded")
    assert deg and deg["dead_ranks"] == [1], deg
    rep = verify_snapshot(snap_dir)
    assert rep.clean and not rep.corrupt, rep
    tgt = {"m": StateDict(**{k: np.zeros_like(v) for k, v in arrays.items()})}
    Snapshot(snap_dir).restore(tgt)
    for k, v in arrays.items():
        assert np.array_equal(tgt["m"][k], v), k
    print("DEGRADED-COMMITTED", flush=True)
    os._exit(0)  # skip the shutdown rendezvous with the dead peer


if __name__ == "__main__":
    from tpusnap.test_utils import run_subprocess_world

    mode, snap = sys.argv[1], sys.argv[2]
    env = {
        "TPUSNAP_LIVENESS_TTL_S": "2.0",
        "TPUSNAP_HEARTBEAT_INTERVAL_S": "0.1",
        "TPUSNAP_DISABLE_BATCHING": "1",
        "TPUSNAP_HISTORY": "0",
        "TPUSNAP_TELEMETRY_DIR": os.path.join(os.path.dirname(snap), "tele"),
    }
    if mode == "abort":
        env["TPUSNAP_FAULT_SPEC"] = (
            "rank=1,transient_per_op=0,crash_after_op=write:1"
        )
    else:
        env["TPUSNAP_RANK_FAILURE"] = "degrade"
    fn = world_fast_abort if mode == "abort" else world_degraded
    try:
        run_subprocess_world(fn, world_size=2, args=[snap], extra_env=env,
                             timeout=120)
    except RuntimeError as e:
        # Rank 1 died by design; rank 0's printed proof rides the logs.
        print(str(e)[-4000:])
'''
world_py = os.path.join(work, "ci_rankfail_world.py")
with open(world_py, "w") as f:
    f.write(WORLD)

# `python world.py` puts the script's own dir (not the repo root this
# gate cd'd into) at sys.path[0] — hand the coordinator the package
# explicitly; the rank children get it from run_subprocess_world.
env = dict(os.environ, JAX_PLATFORMS="cpu",
           PYTHONPATH=os.getcwd(),
           TPUSNAP_TELEMETRY_DIR=os.path.join(work, "tele"),
           TPUSNAP_HISTORY="0")

# (a) fast-abort exit contract.
r = subprocess.run(
    [sys.executable, world_py, "abort", os.path.join(work, "snap_abort")],
    capture_output=True, text=True, env=env, timeout=300,
)
m = re.search(r"RANKFAILED dt=([0-9.]+)", r.stdout)
if r.returncode != 0 or not m:
    die(f"fast-abort leg rc={r.returncode}: {r.stdout[-1200:]}{r.stderr[-600:]}")
dt = float(m.group(1))

# (b) degrade-mode replicated take commits + scrubs clean.
r = subprocess.run(
    [sys.executable, world_py, "degrade", os.path.join(work, "snap_degrade")],
    capture_output=True, text=True, env=env, timeout=300,
)
if r.returncode != 0 or "DEGRADED-COMMITTED" not in r.stdout:
    die(f"degrade leg rc={r.returncode}: {r.stdout[-1200:]}{r.stderr[-600:]}")

print(f"rank-failure smoke: OK (survivor detected the SIGKILLed rank in "
      f"{dt:.1f}s; degraded replicated take committed, scrubbed clean, "
      "restored bit-exact)")
PYEOF
rc=$?
[ "$rc" -eq 0 ] || fail "rank-failure smoke (rc=$rc)" "$rc"

# ---- 11. elastic-stream smoke ---------------------------------------------
echo "ci_gate: [11/16] elastic-stream smoke (2-process stream survives a SIGKILLed rank via a degraded epoch; graceful leave + re-join re-plan the world)"
env JAX_PLATFORMS=cpu TPUSNAP_HISTORY=0 python -m pytest -q \
    tests/test_stream_elastic.py::test_stream_survives_rank_sigkill \
    tests/test_stream_elastic.py::test_stream_graceful_leave_and_rejoin \
    -p no:cacheprovider -p no:xdist -p no:randomly
rc=$?
[ "$rc" -eq 0 ] || fail "elastic-stream smoke (rc=$rc)" "$rc"

# ---- 12. fleet observability smoke ----------------------------------------
echo "ci_gate: [12/16] mini-fleetsim smoke (3 jobs, rank-kill + outage faults; fleet --check exit contract: 0 healthy / 2 breach / 3 no records)"
env JAX_PLATFORMS=cpu python - <<'PYEOF'
import atexit, json, os, shutil, signal, subprocess, sys, tempfile, time

work = tempfile.mkdtemp(prefix="tpusnap_ci_fleet_")
atexit.register(shutil.rmtree, work, True)
fleet_dir = os.path.join(work, "fleet")

def die(msg):
    print(f"mini-fleetsim: FAIL - {msg}", file=sys.stderr)
    sys.exit(1)

def fleet(*extra, check=True):
    return subprocess.run(
        [sys.executable, "-m", "tpusnap", "fleet", "--dir", fleet_dir,
         *(["--check"] if check else []), *extra],
        capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=120,
    )

# (a) empty fleet dir -> exit 3 (no verdict without records).
os.makedirs(fleet_dir)
r = fleet()
if r.returncode != 3:
    die(f"empty dir: expected exit 3, got {r.returncode}: {r.stdout[-300:]}")

# (b) 3 concurrent jobs against one shared fleet dir: a healthy
# trainer, one writing through a seeded 2 s outage window, and one
# SIGKILLed by a chaos rank-kill after its first blob write. Hermetic:
# per-job telemetry dirs under the workdir, HOST history untouched.
_JOB = (
    "import os, sys; os.environ.setdefault('JAX_PLATFORMS','cpu')\n"
    "import jax; jax.config.update('jax_platforms','cpu')\n"
    "import numpy as np\n"
    "from tpusnap import Snapshot, StateDict\n"
    "state={'m': StateDict(w=np.arange(1<<18, dtype=np.float32))}\n"
    "for k in range(2):\n"
    "    Snapshot.take(f'chaos+fs://{sys.argv[1]}/t{k}', state)\n"
)
jobs = []
for name, fault in (
    ("mini-ok", None),
    ("mini-outage", "seed=1,transient_per_op=0,outage=write:0:2"),
    # latency_ms keeps the doomed job alive across a few 50 ms
    # heartbeat ticks so its fleet record exists before the SIGKILL.
    ("mini-killed", "seed=2,transient_per_op=0,latency_ms=300,"
                    "crash_after_op=write:2"),
):
    env = dict(
        os.environ, JAX_PLATFORMS="cpu",
        TPUSNAP_FLEET_DIR=fleet_dir, TPUSNAP_JOB_ID=name,
        TPUSNAP_TELEMETRY_DIR=os.path.join(work, "tele", name),
        TPUSNAP_HISTORY="0", TPUSNAP_HEARTBEAT_INTERVAL_S="0.05",
        TPUSNAP_DISABLE_BATCHING="1",
    )
    if fault:
        env["TPUSNAP_FAULT_SPEC"] = fault
    jobs.append((name, subprocess.Popen(
        [sys.executable, "-c", _JOB, os.path.join(work, "dest", name)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, env=env,
    )))
rcs = {}
for name, p in jobs:
    out, _ = p.communicate(timeout=180)
    rcs[name] = p.returncode
    if name == "mini-killed":
        if p.returncode != -signal.SIGKILL:
            die(f"{name}: expected SIGKILL, got {p.returncode}: {out[-400:]}")
    elif p.returncode != 0:
        die(f"{name}: rc={p.returncode}: {out[-400:]}")

# All three jobs left a record (the killed one non-final) -> healthy
# under generous thresholds -> exit 0.
r = fleet("--rpo", "3600", "--lag-s", "3600", "--json")
if r.returncode != 0:
    die(f"healthy leg: expected exit 0, got {r.returncode}: {r.stdout[-400:]}")
doc = json.loads(r.stdout)
if doc["rollup"]["n_jobs"] < 3:
    die(f"expected >=3 job records, folded {doc['rollup']['n_jobs']}")
killed = [j for j in doc["rollup"]["jobs"] if j["job_id"] == "mini-killed"]
if not killed or killed[0]["final"]:
    die(f"SIGKILLed job must leave a NON-final record: {killed}")

# (c) seeded stale job (non-final record, 15-minute-old commit) + a
# tight --rpo -> breach -> exit 2.
now = time.time()
stale = {
    "v": 1, "job_id": "mini-stale", "pid": 1, "ts": now - 850,
    "rank": 0, "world_size": 1, "state": "running",
    "slo": {"last_commit_ts": now - 900, "started_ts": now - 900,
            "data_at_risk_bytes": 1 << 20},
}
with open(os.path.join(fleet_dir, "mini-stale.json"), "w") as f:
    json.dump(stale, f)
r = fleet("--rpo", "60")
if r.returncode != 2:
    die(f"stale breach: expected exit 2, got {r.returncode}: {r.stdout[-400:]}")
if "mini-stale" not in r.stdout:
    die(f"breach verdict does not name the stale job: {r.stdout[-400:]}")
print("mini-fleetsim: OK (3/3 contract legs across a 3-job fleet)")
PYEOF
rc=$?
[ "$rc" -eq 0 ] || fail "mini-fleetsim smoke (rc=$rc)" "$rc"

# ---- 13. content-addressed store smoke ------------------------------------
echo "ci_gate: [13/16] CAS smoke (two jobs share a base through one store; SIGKILL mid-gc-sweep -> re-run gc converges -> fsck --store exit 0)"
env JAX_PLATFORMS=cpu python - <<'PYEOF'
import atexit, os, shutil, signal, subprocess, sys, tempfile, time

work = tempfile.mkdtemp(prefix="tpusnap_ci_cas_")
atexit.register(shutil.rmtree, work, True)
store = os.path.join(work, "store")

def die(msg):
    print(f"cas-smoke: FAIL - {msg}", file=sys.stderr)
    sys.exit(1)

def run(cmd, env=None, timeout=120):
    return subprocess.run(
        cmd, capture_output=True, text=True, timeout=timeout,
        env=env or dict(os.environ, JAX_PLATFORMS="cpu"),
    )

def cli(*args, env=None):
    return run([sys.executable, "-m", "tpusnap", *args], env=env)

# (a) two sequential jobs take the SAME content through one shared
# store: the second job's payload must dedup to refs (blob count stays
# at one job's worth), both commit, both fsck clean.
_JOB = (
    "import os, sys; os.environ.setdefault('JAX_PLATFORMS','cpu')\n"
    "import jax; jax.config.update('jax_platforms','cpu')\n"
    "import numpy as np\n"
    "from tpusnap import Snapshot, StateDict\n"
    "rng = np.random.default_rng(7)\n"
    "state = {'m': StateDict(**{f'w{i}': rng.standard_normal((128, 128))"
    ".astype(np.float32) for i in range(4)})}\n"
    "Snapshot.take(sys.argv[1], state)\n"
)
env = dict(
    os.environ, JAX_PLATFORMS="cpu", TPUSNAP_CAS_DIR=store,
    TPUSNAP_DISABLE_BATCHING="1", TPUSNAP_HISTORY="0",
    TPUSNAP_TELEMETRY_DIR=os.path.join(work, "tele"),
)
for job in ("jobA", "jobB"):
    r = run([sys.executable, "-c", _JOB, os.path.join(work, job)], env=env)
    if r.returncode != 0:
        die(f"{job} take failed: {r.stderr[-400:]}")
blobs_dir = os.path.join(store, "blobs")
n_blobs = len(os.listdir(blobs_dir))
if n_blobs != 4:
    die(f"expected 4 deduped blobs for 2 jobs x 4 tensors, got {n_blobs}")
r = cli("fsck", "--store", store)
if r.returncode != 0:
    die(f"fsck --store after 2 jobs: expected exit 0, got {r.returncode}: "
        f"{r.stdout[-300:]}{r.stderr[-300:]}")

# (b) job A retires: its dir goes away, its root record and the now
# half-orphaned blobs age past the grace window (backdated mtimes).
shutil.rmtree(os.path.join(work, "jobA"))
old = time.time() - 3600
for sub in ("roots", "blobs"):
    d = os.path.join(store, sub)
    for name in os.listdir(d):
        os.utime(os.path.join(d, name), (old, old))

# (c) SIGKILL mid-gc-sweep: a chaos-wrapped store URL kills the sweeper
# right after its first delete. Its lease is taken with a 1 s TTL so
# the re-run can steal it.
chaos_env = dict(
    env, TPUSNAP_FAULT_SPEC="crash_after_op=delete:1",
    TPUSNAP_CAS_LEASE_TTL_S="1",
)
r = cli("gc", "--store", f"chaos+fs://{store}", "--force", env=chaos_env)
if r.returncode != -signal.SIGKILL:
    die(f"chaos gc: expected SIGKILL, got {r.returncode}: {r.stderr[-400:]}")
time.sleep(1.2)  # let the dead sweeper's lease expire

# (d) re-run gc converges: job A's stale root sweeps, job B's refs keep
# every blob, and the store fscks clean with zero dangling refs.
r = cli("gc", "--store", store, "--force", env=env)
if r.returncode != 0:
    die(f"gc re-run: expected exit 0, got {r.returncode}: {r.stderr[-400:]}")
r = cli("fsck", "--store", store)
if r.returncode != 0:
    die(f"fsck --store after gc: expected exit 0, got {r.returncode}: "
        f"{r.stdout[-300:]}{r.stderr[-300:]}")
if len(os.listdir(blobs_dir)) != 4:
    die(f"job B's refs must keep all 4 blobs, got {len(os.listdir(blobs_dir))}")
r = cli("fsck", os.path.join(work, "jobB"), env=env)
if r.returncode != 0:
    die(f"job B fsck: expected exit 0, got {r.returncode}: {r.stdout[-300:]}")
print("cas-smoke: OK (dedup 2 jobs -> 4 blobs; mid-sweep SIGKILL -> "
      "converged gc -> clean fsck)")
PYEOF
rc=$?
[ "$rc" -eq 0 ] || fail "CAS smoke (rc=$rc)" "$rc"

# ---- 14. optional real-backend cloud suite -------------------------------
if command -v fake-gcs-server >/dev/null 2>&1 || command -v minio >/dev/null 2>&1; then
    echo "ci_gate: [14/16] real-backend cloud suite (fake-gcs-server/minio found on PATH)"
    env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m cloud_real \
        -p no:cacheprovider -p no:xdist -p no:randomly
    rc=$?
    # pytest exit 5 = no tests collected/all skipped (e.g. only one
    # binary present and its client package missing) - not a failure.
    if [ "$rc" -ne 0 ] && [ "$rc" -ne 5 ]; then
        fail "real-backend cloud suite (rc=$rc)" "$rc"
    fi
else
    echo "ci_gate: [14/16] real-backend cloud suite skipped (no fake-gcs-server/minio on PATH)"
fi

# ---- 15. tune smoke ------------------------------------------------------
echo "ci_gate: [15/16] tune smoke (exit contract: 0 plan / 3 insufficient history; TPUSNAP_AUTOTUNE=1 restore stamps the applied plan)"
env JAX_PLATFORMS=cpu python - <<'PYEOF'
import json, os, shutil, subprocess, sys, tempfile

work = tempfile.mkdtemp(prefix="tpusnap_ci_tune_")
tele = os.path.join(work, "tele")
# Hermetic: history lives in the tempdir, never the host's.
env = dict(os.environ, JAX_PLATFORMS="cpu", TPUSNAP_TELEMETRY_DIR=tele)
import atexit
atexit.register(shutil.rmtree, work, True)

def tune(*extra, e=None):
    return subprocess.run(
        [sys.executable, "-m", "tpusnap", "tune", "--check", *extra],
        capture_output=True, text=True, env=e or env, timeout=120,
    )

def die(msg):
    print(f"tune smoke: FAIL - {msg}", file=sys.stderr)
    sys.exit(1)

# (a) empty history -> insufficient comparable events -> exit 3
r = tune(e=dict(env, TPUSNAP_TELEMETRY_DIR=os.path.join(work, "empty")))
if r.returncode != 3:
    die(f"empty history: expected exit 3, got {r.returncode}: "
        f"{r.stdout[-300:]}{r.stderr[-300:]}")

# (b) one real take+restore seeds a genuine restore event (correct
# plugin label), then clones of it give the cell enough evidence; the
# 1 GiB payload makes the probe-cadence rule fire deterministically
# against the 2 GiB default interval.
script = (
    "import os; os.environ.setdefault('JAX_PLATFORMS','cpu')\n"
    "import numpy as np, sys\n"
    "from tpusnap import Snapshot, StateDict\n"
    "s = {'a': StateDict(w=np.arange(200000, dtype=np.float32))}\n"
    "Snapshot.take(sys.argv[1], s)\n"
    "t = {'a': StateDict(w=np.zeros(200000, dtype=np.float32))}\n"
    "Snapshot(sys.argv[1]).restore(t)\n"
)
snap = os.path.join(work, "snap")
subprocess.run([sys.executable, "-c", script, snap],
               check=True, env=env, timeout=180)
hist = os.path.join(tele, "history.jsonl")
events = [json.loads(ln) for ln in open(hist) if ln.strip()]
base = next(e for e in reversed(events) if e.get("kind") == "restore")
with open(hist, "a") as f:
    for _ in range(3):
        seed = dict(base, bytes=1 << 30, wall_s=2.0)
        f.write(json.dumps(seed) + "\n")
r = tune("--kind", "restore")
if r.returncode != 0:
    die(f"seeded history: expected exit 0, got {r.returncode}: "
        f"{r.stdout[-400:]}{r.stderr[-300:]}")
r = tune("--kind", "restore", "--json")
plan = json.loads(r.stdout)
if not plan.get("ok") or not plan.get("plan_id") or not plan.get("knobs"):
    die(f"seeded plan must carry plan_id + knobs: {r.stdout[-400:]}")

# (c) TPUSNAP_AUTOTUNE=1 restore applies the plan and stamps
# `tuned: {plan_id, knobs}` into its history event.
restore = (
    "import os; os.environ.setdefault('JAX_PLATFORMS','cpu')\n"
    "import numpy as np, sys\n"
    "from tpusnap import Snapshot, StateDict\n"
    "t = {'a': StateDict(w=np.zeros(200000, dtype=np.float32))}\n"
    "Snapshot(sys.argv[1]).restore(t)\n"
)
subprocess.run([sys.executable, "-c", restore, snap], check=True,
               env=dict(env, TPUSNAP_AUTOTUNE="1"), timeout=180)
events = [json.loads(ln) for ln in open(hist) if ln.strip()]
last = next(e for e in reversed(events) if e.get("kind") == "restore")
tuned = last.get("tuned")
if not isinstance(tuned, dict) or not tuned.get("plan_id") or not tuned.get("knobs"):
    die(f"autotuned restore event must stamp tuned: {json.dumps(last)[:400]}")
if tuned["plan_id"] != plan["plan_id"]:
    die(f"applied plan_id {tuned['plan_id']} != planned {plan['plan_id']}")
print("tune smoke: OK (exit 3 empty, exit 0 seeded, autotune stamped "
      f"plan {tuned['plan_id']})")
PYEOF
rc=$?
[ "$rc" -eq 0 ] || fail "tune smoke (rc=$rc)" "$rc"

# ---- 16. access-ledger heatmap smoke ------------------------------------
echo "ci_gate: [16/16] heatmap smoke (exit contract: 3 no ledgers / 0 partial read_object coverage / 2 amplification breach)"
env JAX_PLATFORMS=cpu python - <<'PYEOF'
import json, os, shutil, subprocess, sys, tempfile

work = tempfile.mkdtemp(prefix="tpusnap_ci_heatmap_")
tele = os.path.join(work, "tele")
snap = os.path.join(work, "snap")
# Hermetic: ledgers land in the tempdir, never the host's telemetry.
env = dict(os.environ, JAX_PLATFORMS="cpu", TPUSNAP_TELEMETRY="1",
           TPUSNAP_TELEMETRY_DIR=tele)
import atexit
atexit.register(shutil.rmtree, work, True)

def heatmap(*extra, e=None):
    return subprocess.run(
        [sys.executable, "-m", "tpusnap", "heatmap", snap, *extra],
        capture_output=True, text=True, env=e or env, timeout=120,
    )

def die(msg):
    print(f"heatmap smoke: FAIL - {msg}", file=sys.stderr)
    sys.exit(1)

# (a) A snapshot nobody read: no ledgers -> exit 3.
take = (
    "import os; os.environ.setdefault('JAX_PLATFORMS','cpu')\n"
    "import numpy as np, sys\n"
    "from tpusnap import Snapshot, StateDict\n"
    "s = {'m': StateDict(**{f'w{i}': np.arange(4096 + i, dtype=np.float32)\n"
    "                       for i in range(8)})}\n"
    "Snapshot.take(sys.argv[1], s)\n"
)
subprocess.run([sys.executable, "-c", take, snap], check=True, env=env,
               timeout=180)
r = heatmap("--check")
if r.returncode != 3:
    die(f"no ledgers: expected exit 3, got {r.returncode}: "
        f"{r.stdout[-300:]}{r.stderr[-300:]}")

# (b) One partial reader (read_object of ONE of 8 leaves): coverage
# must fall below 100% and the read leaf must be the only one with
# bytes attributed.
read_one = (
    "import os; os.environ.setdefault('JAX_PLATFORMS','cpu')\n"
    "import sys\n"
    "from tpusnap import Snapshot\n"
    "Snapshot(sys.argv[1]).read_object('0/m/w3')\n"
)
subprocess.run([sys.executable, "-c", read_one, snap], check=True,
               env=env, timeout=180)
r = heatmap("--json")
if r.returncode != 0:
    die(f"partial reader: expected exit 0, got {r.returncode}: "
        f"{r.stderr[-300:]}")
doc = json.loads(r.stdout)
if not (0 < doc["coverage"] < 1.0):
    die(f"partial reader: coverage must be in (0,1), got {doc['coverage']}")
touched = [l["path"] for l in doc["leaves"] if l["bytes_read"]]
if touched != ["m/w3"]:
    die(f"partial reader: only m/w3 may carry bytes, got {touched}")
partial_cov = doc["coverage"]

# (c) A 3-reader full-restore cohort: merged amplification ~3x must
# trip a 2.5x --max-amplification gate (exit 2) and pass a 4x one.
restore = (
    "import os; os.environ.setdefault('JAX_PLATFORMS','cpu')\n"
    "import numpy as np, sys\n"
    "from tpusnap import Snapshot, StateDict\n"
    "t = {'m': StateDict(**{f'w{i}': np.zeros(4096 + i, dtype=np.float32)\n"
    "                       for i in range(8)})}\n"
    "Snapshot(sys.argv[1]).restore(t)\n"
)
for k in range(3):
    subprocess.run([sys.executable, "-c", restore, snap], check=True,
                   env=dict(env, TPUSNAP_JOB_ID=f"ci-reader-{k}"),
                   timeout=180)
r = heatmap("--json", "--check", "--max-amplification", "2.5")
if r.returncode != 2:
    die(f"cohort: expected breach exit 2, got {r.returncode}: "
        f"{r.stdout[-300:]}{r.stderr[-300:]}")
doc = json.loads(r.stdout)
if doc["n_readers"] < 4:  # 3 named readers + the read_object job
    die(f"cohort: expected >=4 distinct readers, got {doc['n_readers']}")
if not (doc["coverage"] > 0.99 and doc["amplification"] > 2.5):
    die(f"cohort: coverage {doc['coverage']} / amplification "
        f"{doc['amplification']} out of contract")
r = heatmap("--check", "--max-amplification", "4")
if r.returncode != 0:
    die(f"cohort under a 4x budget: expected exit 0, got {r.returncode}")
print("heatmap smoke: OK (exit 3 no ledgers, partial coverage "
      f"{partial_cov:.2f} -> only m/w3, cohort amplification "
      f"{doc['amplification']:.2f}x gated)")
PYEOF
rc=$?
[ "$rc" -eq 0 ] || fail "heatmap smoke (rc=$rc)" "$rc"

echo "ci_gate: PASS"
