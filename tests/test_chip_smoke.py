"""chip_smoke.py's contract off the chip, the compile-cache helper, and
the native loader's host key — the parts of the bring-up a CPU can check.
What the smoke proves on a TPU is recorded in CHANGES.md / PERF.md."""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_smoke(*args, **env_overrides):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_overrides)
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *args],
        capture_output=True,
        text=True,
        timeout=300,
        cwd=REPO,
        env=env,
    )


def test_smoke_refuses_to_run_without_a_tpu():
    proc = _run_smoke()
    assert proc.returncode not in (0, None)
    assert "platform 'cpu'" in proc.stderr
    assert proc.stdout == ""  # no result line, no train step


def test_rehearsal_runs_on_cpu_and_never_says_ok(tmp_path):
    cache_dir = tmp_path / "cache"
    proc = _run_smoke(
        "--rehearsal",
        XLA_FLAGS="--xla_force_host_platform_device_count=1",
        JAX_COMPILATION_CACHE_DIR=str(cache_dir),
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    lines = proc.stdout.strip().splitlines()
    assert all(
        line.startswith("rehearsal [platform: cpu,") for line in lines[:-1]
    ), proc.stdout
    # Last line: the verdict, with exactly the keys the chip check reads
    # (and "ok" is not one of them off the chip).
    assert json.loads(lines[-1]) == {
        "rehearsal": True,
        "chip": "not run",
        "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }
    # The line before it: what was observed.
    summary = json.loads(lines[-2].split(" summary: ", 1)[1])
    assert summary["legs"]["four_chip"].startswith("not run")
    assert summary["device_pack_fallbacks"] == 0
    assert summary["tpusnap_warnings"] == []
    # The cache went where the environment said, and only there.
    assert summary["compile_cache"]["dir"] == str(cache_dir)
    assert summary["compile_cache"]["entries_after"] == len(os.listdir(cache_dir)) > 0


def test_compile_cache_dir_is_env_or_fixed_checkout_path(monkeypatch):
    import jax

    from tpusnap import compile_cache

    updates = []
    monkeypatch.setattr(
        jax.config, "update", lambda name, value: updates.append((name, value))
    )
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert compile_cache.enable() == "/some/dir"
    assert "jax_compilation_cache_dir" not in dict(updates)  # JAX read the env

    updates.clear()
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    first, second = compile_cache.enable(), compile_cache.enable()
    assert first == second == os.path.join(REPO, ".jax_cache")
    assert dict(updates)["jax_compilation_cache_dir"] == first
    assert dict(updates)["jax_persistent_cache_min_compile_time_secs"] == 0.0


def test_native_loader_builds_for_this_host_beside_a_foreign_binary(
    tmp_path, monkeypatch
):
    """A binary that was not built from this source for this host's CPU
    (the old unkeyed name, another host's key) is never opened: the
    loader builds its own beside it."""
    from tpusnap import _native

    if not _native.available():
        pytest.skip("no native toolchain")
    native_dir = tmp_path / "_native"
    shutil.copytree(
        os.path.join(os.path.dirname(_native.__file__), "src"), native_dir / "src"
    )
    foreign = [
        native_dir / "libtpusnap_native.so",
        native_dir / "libtpusnap_native.0123456789abcdef.so",
    ]
    for path in foreign:
        path.write_bytes(b"not an ELF file: dlopen of this would fail")
    monkeypatch.setattr(_native, "_DIR", str(native_dir))
    monkeypatch.setattr(_native, "_lib", None)
    monkeypatch.setattr(_native, "_load_attempted", False)
    monkeypatch.setattr(_native, "_built_in_process", False)

    info = _native.build_info()
    assert info["loaded"] and info["built_in_process"]
    assert info["path"] == _native.library_path(str(native_dir))
    assert os.path.dirname(info["path"]) == str(native_dir)
    assert info["path"] not in map(str, foreign) and os.path.exists(info["path"])
    assert _native.crc32c(bytes(32)) == 0x8A9136AA
    # The key moves with the source: an edited source is another binary.
    with open(native_dir / "src" / "tpusnap_native.cpp", "a") as f:
        f.write("\n// edited\n")
    assert _native.library_path(str(native_dir)) != info["path"]
