"""Smoke-run the examples as subprocesses — the examples are the canonical
user journeys (reference examples/simple_example.py etc.); an API drift that
breaks them must fail the suite, not a user.
"""

import os
import subprocess
import sys
import tempfile

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_example(name, *args, timeout=300):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()
    # The examples turn the persistent compile cache on; keep it out of
    # the checkout so one test run cannot change the next.
    with tempfile.TemporaryDirectory() as cache_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "examples", name), *args],
            capture_output=True,
            text=True,
            timeout=timeout,
            cwd=REPO,
            env=env,
        )
    assert proc.returncode == 0, (
        f"{name} failed (rc={proc.returncode})\n"
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    )
    return proc.stdout


def test_simple_example_and_resume(tmp_path):
    out = _run_example("simple_example.py", "--work-dir", str(tmp_path))
    assert "epoch 4" in out
    # Resume from epoch 2's snapshot: the loop must continue at epoch 3.
    out = _run_example(
        "simple_example.py",
        "--work-dir",
        str(tmp_path),
        "--resume-from",
        str(tmp_path / "epoch_2"),
    )
    assert "resumed" in out and "at epoch 2" in out
    assert "epoch 3" in out and "epoch 4" in out


def test_transformer_example(tmp_path):
    _run_example("transformer_example.py", "--work-dir", str(tmp_path))
    # Resume from the last epoch snapshot: exercises async_restore
    # (reads overlap setup) in the canonical flagship journey.
    import glob

    snaps = sorted(glob.glob(str(tmp_path / "epoch_*")))
    assert snaps, "example produced no snapshots"
    out = _run_example(
        "transformer_example.py",
        "--work-dir",
        str(tmp_path),
        "--resume-from",
        snaps[-1],
    )
    assert "resumed at epoch" in out


@pytest.mark.distributed
def test_distributed_example(tmp_path):
    _run_example("distributed_example.py", "--work-dir", str(tmp_path))


def test_incremental_example(tmp_path):
    out = _run_example("incremental_example.py", "--work-dir", str(tmp_path))
    assert "incremental on" in out
    assert "0 corrupt" in out
    assert "bit-exact" in out
