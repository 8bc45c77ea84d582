"""Sharded (FSDP-analog) snapshot benchmark: save + restore a mesh-
sharded transformer train state.

Mirrors /root/reference/benchmarks/fsdp/main.py:35-104 (1.9B-param
nn.Transformer under LOCAL_STATE_DICT): the state is genuinely
partitioned — each shard written once by its owner — and restore puts
every shard back onto its device with the target sharding.

Run (8 virtual CPU devices):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python benchmarks/sharded/main.py [--d-model 1024]
"""

import argparse
import os
import sys
import tempfile
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)

import jax
import jax.numpy as jnp

from tpusnap import PytreeState, Snapshot, compile_cache
from tpusnap.models import Transformer, TransformerConfig, make_mesh
from tpusnap.models.transformer import init_train_state


def main() -> None:
    compile_cache.enable()
    parser = argparse.ArgumentParser()
    parser.add_argument("--d-model", type=int, default=1024)
    parser.add_argument("--n-layers", type=int, default=8)
    parser.add_argument(
        "--runs",
        type=int,
        default=3,
        help="samples per phase; the virtio disk swings >2x minute to "
        "minute, so best-of-N is the repeatable number",
    )
    args = parser.parse_args()

    mesh = make_mesh()
    cfg = TransformerConfig(
        vocab_size=32768,
        d_model=args.d_model,
        n_heads=16,
        n_layers=args.n_layers,
        d_ff=4 * args.d_model,
    )
    model = Transformer(cfg)
    state = init_train_state(model, mesh, jax.random.PRNGKey(0))
    nbytes = sum(leaf.nbytes for leaf in jax.tree.leaves(state))
    print(f"train state: {nbytes / 1e9:.2f} GB over mesh {dict(mesh.shape)}")

    take_runs, restore_runs = [], []
    with tempfile.TemporaryDirectory(prefix="tpusnap_bench_shard_") as work_dir:
        for run in range(args.runs):
            path = os.path.join(work_dir, f"snap{run}")
            os.sync()
            t0 = time.perf_counter()
            Snapshot.take(path, {"ts": PytreeState(state)})
            take_runs.append(time.perf_counter() - t0)

            target = PytreeState(jax.tree.map(jnp.zeros_like, state))
            t0 = time.perf_counter()
            Snapshot(path).restore({"ts": target})
            restore_runs.append(time.perf_counter() - t0)

    take_s, restore_s = min(take_runs), min(restore_runs)
    print(f"take:    {take_s:.2f}s ({nbytes / take_s / 1e9:.2f} GB/s) "
          f"runs={[round(t, 2) for t in take_runs]}")
    print(f"restore: {restore_s:.2f}s ({nbytes / restore_s / 1e9:.2f} GB/s) "
          f"runs={[round(t, 2) for t in restore_runs]}")


if __name__ == "__main__":
    main()
