"""Embedding-table (torchrec-analog) snapshot benchmark: sync vs async
take of a sharded embedding collection, with RSS tracking.

Mirrors /root/reference/benchmarks/torchrec/main.py:133-151,211-231
(row-wise DLRM tables, sync-vs-async blocked-time split, RSS deltas
validating the memory budget). Tables are row-wise sharded over the
mesh's model axes; the async variant reports the *blocked* time (until
``async_take`` returns — training could resume here) separately from the
total time (until the background I/O drains).

Run (8 virtual CPU devices):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python benchmarks/embedding/main.py [--rows 1000000]
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

from tpusnap import PytreeState, Snapshot, compile_cache
from tpusnap.models import EmbeddingCollection, TableConfig, make_mesh
from tpusnap.rss_profiler import measure_rss_deltas


def main() -> None:
    compile_cache.enable()
    parser = argparse.ArgumentParser()
    parser.add_argument("--rows", type=int, default=1_000_000)
    parser.add_argument("--dim", type=int, default=64)
    parser.add_argument("--tables", type=int, default=4)
    args = parser.parse_args()

    mesh = make_mesh()
    model = EmbeddingCollection(
        [
            TableConfig(f"table_{i}", args.rows, args.dim, sharding="row")
            for i in range(args.tables)
        ]
    )
    params = model.shard_params(model.init(jax.random.PRNGKey(0)), mesh)
    nbytes = sum(leaf.nbytes for leaf in jax.tree.leaves(params))
    print(
        f"{args.tables} tables x [{args.rows}, {args.dim}] row-wise "
        f"(+ rowwise-adagrad state): {nbytes / 1e9:.2f} GB "
        f"over mesh {dict(mesh.shape)}"
    )

    with tempfile.TemporaryDirectory(prefix="tpusnap_bench_emb_") as work:
        # Warm-up: the first take jit-compiles the device slab-pack
        # program (one-time per slab composition); timing it against the
        # warm async path below would misattribute compile time to the
        # sync pipeline.
        Snapshot.take(os.path.join(work, "warmup"), {"emb": PytreeState(params)})
        os.sync()

        rss_deltas = []
        with measure_rss_deltas(rss_deltas):
            t0 = time.perf_counter()
            Snapshot.take(os.path.join(work, "sync"), {"emb": PytreeState(params)})
            sync_s = time.perf_counter() - t0
        print(
            f"sync take:  {sync_s:.2f}s ({nbytes / sync_s / 1e9:.2f} GB/s), "
            f"peak RSS delta {max(rss_deltas) / 1e6:.0f} MB"
        )

        t0 = time.perf_counter()
        pending = Snapshot.async_take(
            os.path.join(work, "async"), {"emb": PytreeState(params)}
        )
        blocked_s = time.perf_counter() - t0
        pending.wait()
        total_s = time.perf_counter() - t0
        print(
            f"async take: blocked {blocked_s:.2f}s / total {total_s:.2f}s "
            f"(training stalls {blocked_s / total_s:.0%} of the snapshot)"
        )

        target = PytreeState(params)
        t0 = time.perf_counter()
        Snapshot(os.path.join(work, "sync")).restore({"emb": target})
        restore_s = time.perf_counter() - t0
        print(f"restore:    {restore_s:.2f}s ({nbytes / restore_s / 1e9:.2f} GB/s)")


if __name__ == "__main__":
    main()
