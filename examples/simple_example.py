"""Canonical tpusnap usage: an epoch loop with resumable app state.

Mirrors /root/reference/examples/simple_example.py:50-82 — train a tiny
model, snapshot every epoch, kill/resume from the latest snapshot.

Run: python examples/simple_example.py [--resume-from PATH]
"""

import argparse
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import optax

from tpusnap import PytreeState, RNGState, Snapshot, StateDict, compile_cache

NUM_EPOCHS = 4


def init_model(key):
    k1, k2 = jax.random.split(key)
    return {
        "w": jax.random.normal(k1, (32, 16)) * 0.1,
        "b": jnp.zeros(16),
        "out": jax.random.normal(k2, (16, 1)) * 0.1,
    }


@jax.jit
def loss_fn(params, x, y):
    h = jnp.tanh(x @ params["w"] + params["b"])
    pred = h @ params["out"]
    return jnp.mean((pred - y) ** 2)


def main() -> None:
    compile_cache.enable()
    parser = argparse.ArgumentParser()
    parser.add_argument("--work-dir", default=None)
    parser.add_argument("--resume-from", default=None)
    args = parser.parse_args()
    work_dir = args.work_dir or tempfile.mkdtemp(prefix="tpusnap_example_")

    tx = optax.adam(1e-2)
    params = init_model(jax.random.key(0))
    opt_state = tx.init(params)

    train = PytreeState({"params": params, "opt": opt_state})
    progress = StateDict(epoch=0)
    app_state = {"train": train, "progress": progress, "rng": RNGState()}

    if args.resume_from:
        Snapshot(args.resume_from).restore(app_state)
        print(f"resumed from {args.resume_from} at epoch {progress['epoch']}")

    grad_fn = jax.jit(jax.grad(loss_fn))
    x = np.random.default_rng(0).standard_normal((64, 32)).astype(np.float32)
    y = np.random.default_rng(1).standard_normal((64, 1)).astype(np.float32)

    while progress["epoch"] < NUM_EPOCHS:
        state = train.tree
        grads = grad_fn(state["params"], x, y)
        updates, new_opt = tx.update(grads, state["opt"])
        new_params = optax.apply_updates(state["params"], updates)
        train.tree = {"params": new_params, "opt": new_opt}
        progress["epoch"] += 1

        snap_path = f"{work_dir}/epoch_{progress['epoch']}"
        Snapshot.take(snap_path, app_state)
        loss = float(loss_fn(new_params, x, y))
        print(f"epoch {progress['epoch']}: loss={loss:.5f} snapshot={snap_path}")

    print(f"done. latest snapshot: {work_dir}/epoch_{NUM_EPOCHS}")


if __name__ == "__main__":
    main()
