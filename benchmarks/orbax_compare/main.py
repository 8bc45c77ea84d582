"""Head-to-head vs orbax.checkpoint: save + restore a sharded train state.

The reference benchmarks itself against the incumbent checkpoint path of
its ecosystem (torch.save in benchmarks/ddp, DeepSpeed's native
checkpoint in /root/reference/benchmarks/deepspeed_opt/main.py:27-128).
The JAX ecosystem's incumbent is orbax.checkpoint, so this harness saves
and restores the SAME mesh-sharded transformer train state through both
frameworks and reports wall-clock for each — against BOTH orbax
configurations:

- ``orbax-legacy``: synchronous ``PyTreeCheckpointer`` (the simple API
  many codebases still call);
- ``orbax-prod``: ``AsyncCheckpointer`` + OCDBT + zarr3 — the
  configuration orbax documents for production training loops. For the
  async pair (orbax-prod save vs tpusnap ``async_take``) the table
  reports BLOCKED time (how long training is stopped — the number an
  async checkpointer exists to minimize) and TOTAL time (until the
  snapshot is durable) separately.

Protocol (ROADMAP 5b / VERDICT r5 "weak #4"): every sample cell is one
of ``--runs`` (default 5) INTERLEAVED sessions — tpusnap and both orbax
configs alternate within one disk window per run, so neither framework
monopolizes a fast (or slow) phase of the virtio disk's multi-x swings
— and the HEADLINE statistic is the per-cell **median**, not best-of-N
(best-of-N systematically flatters whichever framework got more
lottery tickets; the median is the honest center). Per-run samples and
best-of-N are still printed for comparability with older rounds, and
the medians are recorded as a ``kind="orbax"`` event in the cross-run
history (fields ``orbax_*``/``ts_*``) so `tpusnap history` can trend
the comparison.

Run (8 virtual CPU devices):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python benchmarks/orbax_compare/main.py [--d-model 1024]
"""

import argparse
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)

import jax

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
        default=5,
        help="interleaved sessions per cell (≥5 for the median "
        "protocol; the virtio disk swings >2x minute to minute, so "
        "the frameworks alternate within one window and the median "
        "over sessions is the headline)",
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

    import orbax.checkpoint as ocp

    legacy = ocp.PyTreeCheckpointer()
    # Production orbax: async save, OCDBT aggregation, zarr3.
    prod = ocp.AsyncCheckpointer(
        ocp.PyTreeCheckpointHandler(use_ocdbt=True, use_zarr3=True)
    )
    shardings = jax.tree.map(lambda x: x.sharding, state)
    restore_args = jax.tree.map(
        lambda s: ocp.ArrayRestoreArgs(sharding=s), shardings
    )

    def restore_kwargs():
        return dict(
            restore_args=ocp.args.PyTreeRestore(restore_args=restore_args)
            if hasattr(ocp, "args")
            else None
        )

    # name -> list of samples
    res = {
        k: []
        for k in (
            "ts_save", "ts_load", "ts_async_blocked", "ts_async_total",
            "legacy_save", "legacy_load",
            "prod_blocked", "prod_total", "prod_load",
        )
    }
    work = tempfile.mkdtemp(prefix="tpusnap_bench_orbax_")
    try:
        for run in range(args.runs):
            # --- tpusnap sync
            ts_dir = os.path.join(work, f"tpusnap{run}")
            os.sync()
            t0 = time.perf_counter()
            Snapshot.take(ts_dir, {"ts": PytreeState(state)})
            res["ts_save"].append(time.perf_counter() - t0)
            target = PytreeState(jax.tree.map(lambda x: x, state))
            t0 = time.perf_counter()
            Snapshot(ts_dir).restore({"ts": target})
            res["ts_load"].append(time.perf_counter() - t0)

            # --- tpusnap async (the pair for orbax-prod's async save)
            tsa_dir = os.path.join(work, f"tpusnap_async{run}")
            os.sync()
            t0 = time.perf_counter()
            pending = Snapshot.async_take(tsa_dir, {"ts": PytreeState(state)})
            res["ts_async_blocked"].append(time.perf_counter() - t0)
            pending.wait()
            res["ts_async_total"].append(time.perf_counter() - t0)

            # --- orbax legacy (sync PyTreeCheckpointer)
            ox_dir = os.path.join(work, f"orbax{run}")
            os.sync()
            t0 = time.perf_counter()
            legacy.save(ox_dir, state)
            res["legacy_save"].append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            legacy.restore(ox_dir, **restore_kwargs())
            res["legacy_load"].append(time.perf_counter() - t0)

            # --- orbax production (AsyncCheckpointer + OCDBT + zarr3)
            oxp_dir = os.path.join(work, f"orbax_prod{run}")
            os.sync()
            t0 = time.perf_counter()
            prod.save(oxp_dir, state)
            res["prod_blocked"].append(time.perf_counter() - t0)
            prod.wait_until_finished()
            res["prod_total"].append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            prod.restore(oxp_dir, **restore_kwargs())
            res["prod_load"].append(time.perf_counter() - t0)
    finally:
        prod.close()
        shutil.rmtree(work, ignore_errors=True)

    from statistics import median

    med = {k: median(v) for k, v in res.items()}
    best = {k: min(v) for k, v in res.items()}

    def row(name, seconds, best_s, note=""):
        print(
            f"{name:24s} {seconds:7.2f}s  {nbytes / seconds / 1e9:6.2f} GB/s"
            f"  (best {best_s:.2f}s)" + (f"  {note}" if note else "")
        )

    print(
        f"samples per cell: {args.runs} interleaved session(s); "
        "MEDIAN shown (best-of-N in parentheses for round-to-round "
        "comparability)"
    )
    row("tpusnap save", med["ts_save"], best["ts_save"])
    row("tpusnap async blocked", med["ts_async_blocked"],
        best["ts_async_blocked"], "training stalled for this long")
    row("tpusnap async total", med["ts_async_total"], best["ts_async_total"])
    row("tpusnap restore", med["ts_load"], best["ts_load"])
    row("orbax-legacy save", med["legacy_save"], best["legacy_save"],
        "PyTreeCheckpointer")
    row("orbax-legacy restore", med["legacy_load"], best["legacy_load"])
    row("orbax-prod blocked", med["prod_blocked"], best["prod_blocked"],
        "AsyncCheckpointer+OCDBT+zarr3")
    row("orbax-prod total", med["prod_total"], best["prod_total"])
    row("orbax-prod restore", med["prod_load"], best["prod_load"])
    speedups = {
        "legacy_save": med["legacy_save"] / med["ts_save"],
        "legacy_restore": med["legacy_load"] / med["ts_load"],
        "prod_blocked": med["prod_blocked"] / med["ts_async_blocked"],
        "prod_total": med["prod_total"] / med["ts_async_total"],
        "prod_restore": med["prod_load"] / med["ts_load"],
    }
    print(
        "speedups vs orbax-legacy (median/median): "
        f"save {speedups['legacy_save']:.2f}x, "
        f"restore {speedups['legacy_restore']:.2f}x"
    )
    print(
        "speedups vs orbax-prod (median/median):   "
        f"blocked {speedups['prod_blocked']:.2f}x, "
        f"total {speedups['prod_total']:.2f}x, "
        f"restore {speedups['prod_restore']:.2f}x"
    )
    print("runs:", {k: [round(t, 2) for t in v] for k, v in res.items()})

    # Record the medians into the cross-run history under its OWN kind
    # ("orbax", not "bench"): check_regression's comparability filter
    # matches kind/rank/world_size only, so sharing kind="bench" with
    # bench.py's large-workload events would let this smaller workload's
    # throughput grade against theirs and fire spurious regressions.
    # Queryable/gateable via `tpusnap history --kind orbax
    # --metric orbax_speedup_save`.
    try:
        from tpusnap import history as _hist

        _hist.record_event(
            {
                "v": 1,
                "ts": round(time.time(), 3),
                "kind": "orbax",
                "bench": "orbax_compare",
                "rank": 0,
                "world_size": 1,
                "bytes": nbytes,
                "sessions": args.runs,
                "wall_s": round(med["ts_save"], 3),
                "throughput_gbps": round(nbytes / med["ts_save"] / 1e9, 3),
                **{
                    f"{k}_median_s": round(v, 3) for k, v in med.items()
                },
                "orbax_speedup_save": round(speedups["legacy_save"], 3),
                "orbax_speedup_restore": round(
                    speedups["legacy_restore"], 3
                ),
                "orbax_prod_speedup_blocked": round(
                    speedups["prod_blocked"], 3
                ),
                "orbax_prod_speedup_total": round(
                    speedups["prod_total"], 3
                ),
                "orbax_prod_speedup_restore": round(
                    speedups["prod_restore"], 3
                ),
            }
        )
    except Exception as e:
        # The trend is the point of the protocol change — a silently
        # unrecorded run would only be noticed rounds later.
        print(f"WARNING: orbax history event not recorded: {e!r}", file=sys.stderr)


if __name__ == "__main__":
    main()
