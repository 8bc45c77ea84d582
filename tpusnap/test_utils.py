"""Distributed test harness + state-dict equality helpers.

Counterpart of /root/reference/torchsnapshot/test_utils.py. The
reference's key trick (test_utils.py:183-265) launches each test function
under torch elastic as a single-node N-process gloo job; the TPU-native
equivalent spawns N subprocesses that each call
``jax.distributed.initialize`` against a shared coordinator on the CPU
platform — giving a REAL multi-process, multi-device JAX runtime (arrays
spanning processes are genuinely non-fully-addressable) without TPU
hardware.

Usage in tests::

    def _my_world_fn():           # top-level, importable
        import jax ...            # jax.distributed is already initialized

    def test_thing():
        run_subprocess_world(_my_world_fn, world_size=2)

Each subprocess re-imports the function's module and calls it by
qualname (same re-import trick as the reference, test_utils.py:221-224).
"""

from __future__ import annotations

import importlib
import os
import socket
import subprocess
import sys
from typing import Any, Callable, Dict, List, Optional

import numpy as np

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def rand_array(dtype_str: str, shape=(16, 9), seed: int = 0) -> np.ndarray:
    """Random array of any supported dtype with full bit diversity
    (reference rand_tensor, test_utils.py:104-144)."""
    from .serialization import string_to_dtype

    rng = np.random.default_rng(seed)
    dtype = string_to_dtype(dtype_str)
    if dtype_str == "bool":
        return rng.integers(0, 2, size=shape).astype(bool)
    if dtype_str.startswith(("float", "bfloat")):
        return rng.standard_normal(shape).astype(dtype)
    if dtype_str.startswith("complex"):
        return (rng.standard_normal(shape) + 1j * rng.standard_normal(shape)).astype(
            dtype
        )
    raw = rng.integers(0, 256, size=(*shape, dtype.itemsize), dtype=np.uint8)
    return raw.view(dtype).reshape(*shape, -1)[..., 0].copy()


def check_state_dict_eq(a: Any, b: Any) -> bool:
    """Array-aware deep equality over nested state (reference
    check_state_dict_eq, test_utils.py:41-101)."""
    import jax

    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    if ta != tb or len(la) != len(lb):
        return False
    for x, y in zip(la, lb):
        if hasattr(x, "shape") or hasattr(y, "shape"):
            xa, ya = np.asarray(x), np.asarray(y)
            if xa.dtype != ya.dtype or xa.shape != ya.shape:
                return False
            if xa.tobytes() != ya.tobytes():
                return False
        elif x != y:
            return False
    return True


def find_free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def run_subprocess_world(
    fn: Callable[[], None],
    world_size: int,
    devices_per_process: int = 2,
    timeout: float = 180.0,
    extra_env: Optional[Dict[str, str]] = None,
    args: Optional[List[str]] = None,
    hostnames: Optional[List[str]] = None,
) -> List[str]:
    """Run ``fn`` in ``world_size`` jax.distributed-initialized processes.
    Returns each rank's stdout; raises with full logs if any rank fails.

    CPU-only by construction: every child is started with
    ``JAX_PLATFORMS=cpu``. On a TPU host the unit is one process driving
    all visible chips (``chip_smoke.py``); there is no multi-process
    launcher for chips.

    ``hostnames`` simulates a MULTI-HOST topology on one machine: rank i
    runs with ``TPUSNAP_NODE_NAME=hostnames[i]``, which the per-host
    memory-budget divisor and take's G1 hostname gather read in place of
    the OS hostname — the closest honest approximation of the
    reference's multi-node scaling available without real nodes."""
    port = find_free_port()
    coordinator = f"127.0.0.1:{port}"
    procs = []
    env_base = dict(os.environ)
    # The subprocess must be able to re-import fn's defining module even
    # when it lives outside the repo (a user's own script directory).
    module = sys.modules.get(fn.__module__)
    module_dir = ""
    module_name = fn.__module__
    if module is not None and getattr(module, "__file__", None):
        module_path = os.path.abspath(module.__file__)
        module_dir = os.path.dirname(module_path)
        if module_name == "__main__":
            # fn was defined in a directly-run script; the subprocess must
            # re-import it by file name, not as "__main__" (which would be
            # tpusnap.test_utils's own entry point there).
            module_name = os.path.splitext(os.path.basename(module_path))[0]
    for rank in range(world_size):
        env = dict(env_base)
        env.update(
            {
                "PYTHONPATH": _REPO_ROOT,
                "JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices_per_process}",
                "TPUSNAP_TEST_COORDINATOR": coordinator,
                "TPUSNAP_TEST_WORLD_SIZE": str(world_size),
                "TPUSNAP_TEST_RANK": str(rank),
                "TPUSNAP_TEST_MODULE_DIR": module_dir,
            }
        )
        if hostnames is not None:
            env["TPUSNAP_NODE_NAME"] = hostnames[rank]
        if extra_env:
            env.update(extra_env)
        procs.append(
            subprocess.Popen(
                [
                    sys.executable,
                    "-m",
                    "tpusnap.test_utils",
                    module_name,
                    fn.__qualname__,
                    *(args or []),
                ],
                env=env,
                cwd=_REPO_ROOT,
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
            )
        )
    outputs = []
    failed = []
    for rank, proc in enumerate(procs):
        try:
            out, _ = proc.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            proc.kill()
            out, _ = proc.communicate()
            out += "\n<TIMED OUT>"
        outputs.append(out)
        if proc.returncode != 0:
            failed.append(rank)
    if failed:
        logs = "\n".join(
            f"----- rank {r} (exit {procs[r].returncode}) -----\n{outputs[r]}"
            for r in range(world_size)
        )
        raise RuntimeError(f"Ranks {failed} failed:\n{logs}")
    return outputs


def _subprocess_main() -> None:
    module_name, qualname = sys.argv[1], sys.argv[2]
    # These vars are subprocess-harness plumbing (run_multiprocess →
    # child), not knobs, so they are waived from the knob-access lint.
    coordinator = os.environ["TPUSNAP_TEST_COORDINATOR"]  # tpusnap: waive=TPS001 harness plumbing
    world_size = int(os.environ["TPUSNAP_TEST_WORLD_SIZE"])  # tpusnap: waive=TPS001 harness plumbing
    rank = int(os.environ["TPUSNAP_TEST_RANK"])  # tpusnap: waive=TPS001 harness plumbing

    import jax

    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=world_size,
        process_id=rank,
    )
    # tests/ modules are importable from the repo root; user modules from
    # wherever the launching function was defined.
    sys.path.insert(0, os.path.join(_REPO_ROOT, "tests"))
    module_dir = os.environ.get("TPUSNAP_TEST_MODULE_DIR")  # tpusnap: waive=TPS001 harness plumbing
    if module_dir:
        sys.path.insert(0, module_dir)
    module = importlib.import_module(module_name)
    fn = module
    for part in qualname.split("."):
        fn = getattr(fn, part)
    fn(*sys.argv[3:])


if __name__ == "__main__":
    _subprocess_main()
