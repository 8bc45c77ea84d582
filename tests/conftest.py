"""Test configuration: force an 8-device CPU platform so sharding tests can
exercise real multi-device meshes without TPU hardware. Tests never reach
for a chip; ``chip_smoke.py`` is what runs on one.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # for any subprocesses tests spawn
# Run the whole suite under the lock-order watchdog (set before any
# test imports tpusnap — the package auto-installs the instrumentation
# at import when this is on), so tier-1 doubles as a deadlock detector.
# pytest_sessionfinish below fails the session on any reported cycle.
# Override with TPUSNAP_LOCKCHECK=0 to measure the uninstrumented suite.
os.environ.setdefault("TPUSNAP_LOCKCHECK", "1")
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import pytest  # noqa: E402


@pytest.fixture(params=[False, True], ids=["batching_on", "batching_off"])
def toggle_batching(request):
    """Run a test under both batching modes (reference tests/conftest.py:15-18)."""
    from tpusnap.knobs import override_batching_disabled

    with override_batching_disabled(request.param):
        yield request.param


def pytest_sessionfinish(session, exitstatus):
    """Lock-order gate: the whole suite ran under TPUSNAP_LOCKCHECK=1
    (unless explicitly disabled); any AB/BA cycle in the accumulated
    lock-order graph is a potential deadlock and fails the session —
    the PR 6 tier-1 hang, caught as a report instead of a timeout."""
    try:
        from tpusnap.devtools import lockwatch
    except Exception:
        return
    watch = lockwatch.active_watch()
    if watch is None:
        return
    report = watch.render()
    print(f"\n{report}")
    if watch.cycles():
        print(
            "lockwatch: lock-order cycle(s) detected during the test "
            "session — failing the run (see the cycle report above)"
        )
        session.exitstatus = 1
