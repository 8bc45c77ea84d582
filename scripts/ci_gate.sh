#!/usr/bin/env bash
# The gate a CI job or a cron box runs, one command a step:
#
#   1. `tpusnap lint --check`: the AST invariants over the package
#      (knob access, monotonic clocks, sidecar constants, silent
#      swallows, blocking calls in async code, knob/doc drift)
#   2. the tier-1 tests (ROADMAP.md's verify command; conftest runs them
#      with TPUSNAP_LOCKCHECK=1, so a lock-order cycle fails the session)
#   3. the `cloud_real` tests against the real server binaries, only
#      where `fake-gcs-server` or `minio` is on PATH
#
# Every exit-code contract of a subcommand and every SIGKILL-then-recover
# sequence is a tier-1 test beside the module it exercises. Speed is
# measured on the chip: `python3 perf/run.py`, see perf/README.md.
#
# TPUSNAP_CI_SKIP_TESTS=1 skips step 2. Exit: the first failing step's.

set -u -o pipefail
cd "$(dirname "$0")/.."

fail() { echo "ci_gate: FAIL: $1 (rc=$2)" >&2; exit "$2"; }

echo "ci_gate: [1/3] lint --check"
env JAX_PLATFORMS=cpu python -m tpusnap lint --check || fail "tpusnap lint --check" $?

if [ "${TPUSNAP_CI_SKIP_TESTS:-0}" != "1" ]; then
    echo "ci_gate: [2/3] tier-1 tests"
    # cloud_real belongs to step 3 on a host that has the binaries.
    timeout -k 10 1470 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
        -m 'not slow and not cloud_real' --continue-on-collection-errors \
        -p no:cacheprovider -p xdist -n 6 --dist loadfile -p no:randomly \
        || fail "tier-1 tests" $?
else
    echo "ci_gate: [2/3] tier-1 tests skipped (TPUSNAP_CI_SKIP_TESTS=1)"
fi

if command -v fake-gcs-server >/dev/null 2>&1 || command -v minio >/dev/null 2>&1; then
    echo "ci_gate: [3/3] cloud_real tests (fake-gcs-server/minio on PATH)"
    env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m cloud_real \
        -p no:cacheprovider -p no:xdist -p no:randomly
    rc=$?
    # pytest's 5 = nothing collected (a binary without its client package)
    if [ "$rc" -ne 0 ] && [ "$rc" -ne 5 ]; then fail "cloud_real tests" "$rc"; fi
else
    echo "ci_gate: [3/3] cloud_real tests skipped (no fake-gcs-server/minio on PATH)"
fi

echo "ci_gate: PASS"
