"""Incremental checkpointing + integrity workflow.

The shape this exists for: a model with a large frozen component (a
pretrained tower / embedding table) and a small trained head. Naive
checkpointing rewrites the frozen gigabytes every step; incremental
snapshots hash them (~19 GB/s) and write only the changed head.

The loop below takes a full snapshot once, then layers incremental
snapshots on it each "epoch", verifies the latest with the integrity
scrub, and finally materializes it (copies the base-referenced blobs in)
so older snapshots can be deleted under a retention policy.

Run: python examples/incremental_example.py [--work-dir DIR]
"""

import argparse
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax.numpy as jnp
import numpy as np

from tpusnap import PytreeState, Snapshot, StateDict, compile_cache

NUM_EPOCHS = 3


def main() -> None:
    compile_cache.enable()
    parser = argparse.ArgumentParser()
    parser.add_argument("--work-dir", default=None)
    args = parser.parse_args()
    work_dir = args.work_dir or tempfile.mkdtemp(prefix="tpusnap_inc_example_")

    # Large frozen component + small trained head.
    frozen_tower = np.random.default_rng(0).standard_normal(
        (4096, 512)
    ).astype(np.float32)
    head = jnp.zeros((512, 8), dtype=jnp.float32)

    def snap_path(step: int) -> str:
        return os.path.join(work_dir, f"step_{step}")

    def du(path: str) -> int:
        return sum(
            os.path.getsize(os.path.join(d, f))
            for d, _, fs in os.walk(path)
            for f in fs
        )

    prev = None
    for epoch in range(NUM_EPOCHS):
        head = head + 0.01 * (epoch + 1)  # "training" updates the head only
        app_state = {
            "model": PytreeState({"frozen": frozen_tower, "head": head}),
            "progress": StateDict(epoch=epoch),
        }
        path = snap_path(epoch)
        Snapshot.take(path, app_state, incremental_from=prev)
        kind = "full" if prev is None else f"incremental on {prev}"
        print(f"epoch {epoch}: snapshot {path} ({kind}, {du(path) / 1e6:.1f} MB)")
        if prev is not None:
            # The dedup must actually have happened: an increment holds
            # only the changed head, a small fraction of the full size.
            assert du(path) < du(snap_path(0)) / 10, (du(path), du(snap_path(0)))
        prev = path

    # Verify the latest snapshot end to end (every byte, incl. the blobs
    # it references inside step_0).
    latest = snap_path(NUM_EPOCHS - 1)
    report = Snapshot(latest).verify()
    print(f"verify {latest}: {report.summary()}")
    assert report.clean

    # Retention: keep only the newest snapshot. apply_retention
    # materializes it (copies the base-referenced blobs in, verified)
    # BEFORE deleting the older snapshots it depended on.
    from tpusnap.retention import apply_retention

    plan = apply_retention(work_dir, keep_last=1)
    print(f"retention: {plan.summary()}")
    assert plan.bytes_copied >= frozen_tower.nbytes
    assert os.listdir(work_dir) == [os.path.basename(latest)]

    # The survivor still restores bit-exactly.
    target = {
        "model": PytreeState(
            {
                "frozen": np.zeros_like(frozen_tower),
                "head": jnp.zeros((512, 8), dtype=jnp.float32),
            }
        ),
        "progress": StateDict(epoch=-1),
    }
    Snapshot(latest).restore(target)
    assert target["progress"]["epoch"] == NUM_EPOCHS - 1
    assert np.array_equal(target["model"].tree["frozen"], frozen_tower)
    assert np.array_equal(np.asarray(target["model"].tree["head"]), np.asarray(head))
    assert Snapshot(latest).verify().clean
    print("restore after retention: bit-exact; survivor scrubs clean")


if __name__ == "__main__":
    main()
