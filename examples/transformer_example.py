"""Flagship workload: mesh-sharded transformer training with async
snapshots every epoch and resumable state.

Brings together the whole framework on one model:
- params/optimizer sharded over a ("data", "fsdp", "tensor") mesh
  (dp/fsdp/tp; MoE experts over "data" = ep; optional ring attention
  over "fsdp" = sp/cp),
- ``Snapshot.async_take`` so training resumes while storage I/O drains
  (reference examples + async path, snapshot.py:242-315),
- elastic restore: the snapshot can be restored under a different mesh
  shape (manifest-level resharding).

Run (8 virtual CPU devices):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/transformer_example.py
"""

import argparse
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np

from tpusnap import PytreeState, Snapshot, StateDict, compile_cache
from tpusnap.models import Transformer, TransformerConfig, make_mesh, make_train_step
from tpusnap.models.transformer import init_train_state, random_tokens

NUM_EPOCHS = 3
STEPS_PER_EPOCH = 4


def main() -> None:
    compile_cache.enable()
    parser = argparse.ArgumentParser()
    parser.add_argument("--work-dir", default=None)
    parser.add_argument("--resume-from", default=None)
    args = parser.parse_args()
    work_dir = args.work_dir or tempfile.mkdtemp(prefix="tpusnap_xf_")

    mesh = make_mesh()
    use_ring = mesh.shape["fsdp"] > 1
    cfg = TransformerConfig(
        vocab_size=512,
        d_model=128,
        n_heads=8,
        n_layers=2,
        d_ff=256,
        n_experts=4,
        use_ring_attention=use_ring,
    )
    model = Transformer(cfg)
    state = init_train_state(model, mesh, jax.random.PRNGKey(0))
    train_step = make_train_step(model, mesh, learning_rate=1e-2)

    train = PytreeState(state)
    progress = StateDict(epoch=0)
    app_state = {"train": train, "progress": progress}
    if args.resume_from:
        # Background restore: storage reads overlap the train-step
        # compilation below; app_state must not be touched until wait().
        pending_restore = Snapshot(args.resume_from).async_restore(app_state)
    else:
        pending_restore = None

    rng = np.random.default_rng(0)
    if pending_restore is not None:
        pending_restore.wait()  # reads overlapped the setup above
        print(f"resumed at epoch {progress['epoch']}")
    pending = None
    while progress["epoch"] < NUM_EPOCHS:
        state = train.tree
        for _ in range(STEPS_PER_EPOCH):
            tokens = random_tokens(cfg, mesh, rng, batch=4, seq_len=32)
            state, loss = train_step(state, tokens)
        train.tree = state
        progress["epoch"] += 1

        if pending is not None:
            pending.wait()  # previous epoch's I/O must finish first
        snap_path = f"{work_dir}/epoch_{progress['epoch']}"
        pending = Snapshot.async_take(snap_path, app_state)
        print(
            f"epoch {progress['epoch']}: loss={float(loss):.4f} "
            f"async snapshot -> {snap_path}"
        )

    if pending is None:
        print("nothing to train (resumed at final epoch)")
        return
    snapshot = pending.wait()
    print(f"done; final snapshot committed: {snapshot.path}")


if __name__ == "__main__":
    main()
