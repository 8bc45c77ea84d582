#!/usr/bin/env python3
"""Does a step run beside a pending device-to-host copy on this runtime?

One process, one chip, no tpusnap in it. ``--leaves`` device arrays of
``--leaf-mib`` MiB; a jitted step that reads none of them; ``--steps``
steps in a row, each ended by ``block_until_ready``, timed from the moment
the first copy is started. One JSON line a case:

- ``quiet``: no copy at all;
- ``all_at_once``: ``copy_to_host_async`` on every leaf, then a thread
  that fetches them in order with ``np.asarray`` (what ``prepare`` did
  until PR 41);
- ``all_at_once_unfetched``: the same copies, nobody fetching them until
  the steps are done (is it the fetch that holds the step?);
- ``ahead_<k>``: the fetching thread starts leaf i and the k after it
  just before it fetches leaf i (what ``_WriteScheduler`` does since
  PR 41: ``ahead_1`` is PR 37's "two ahead", leaf i and i+1).

``sum_ms`` is the steps' wall time; ``landed_ms`` when each leaf was on
the host. A case's counts (copies started, leaves fetched, bytes, steps)
print on every backend; its times print as null unless the first device
is an accelerator: a CPU's "copy" is a view and its times mean nothing.

    chiprun -- python3 scripts/dtoh_overlap_probe.py
    JAX_PLATFORMS=cpu python3 scripts/dtoh_overlap_probe.py --leaves 4 \
        --leaf-mib 1 --steps 3 --step-iters 2      # counts only
"""

from __future__ import annotations

import argparse
import json
import threading
import time
from typing import List, Optional


def _cases(ahead: List[int]) -> List[str]:
    return ["quiet", "all_at_once", "all_at_once_unfetched"] + [
        f"ahead_{k}" for k in ahead
    ]


def run_case(name: str, arrs, step, n_steps: int, timed: bool) -> dict:
    """Run ``n_steps`` steps beside the copies that ``name`` asks for and
    return the case's line."""
    import numpy as np

    now = time.monotonic
    started = [False] * len(arrs)
    landed: List[int] = []
    t0 = now()

    def start(i: int) -> None:
        if not started[i]:
            started[i] = True
            arrs[i].copy_to_host_async()

    def fetch(ahead: Optional[int]) -> None:
        for i, a in enumerate(arrs):
            if ahead is not None:
                for j in range(i, min(i + ahead + 1, len(arrs))):
                    start(j)
            np.asarray(a)
            landed.append(round((now() - t0) * 1e3))

    fetcher = None
    if name.startswith("all_at_once"):
        for i in range(len(arrs)):
            start(i)
        if name == "all_at_once":
            fetcher = threading.Thread(target=fetch, args=(None,))
    elif name.startswith("ahead_"):
        fetcher = threading.Thread(target=fetch, args=(int(name[6:]),))
    if fetcher is not None:
        fetcher.start()
    steps_ms = []
    for _ in range(n_steps):
        t = now()
        step().block_until_ready()
        steps_ms.append(round((now() - t) * 1e3, 1))
    if fetcher is not None:
        fetcher.join()
    if name == "all_at_once_unfetched":
        fetch(None)
    nbytes = sum(a.nbytes for a, s in zip(arrs, started) if s)
    return {
        "case": name,
        "copies_started": sum(started),
        "leaves_fetched": len(landed),
        "bytes_started": nbytes,
        "steps": n_steps,
        "steps_ms": steps_ms if timed else None,
        "sum_ms": round(sum(steps_ms)) if timed else None,
        "landed_ms": landed if timed else None,
    }


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--leaves", type=int, default=14)
    ap.add_argument("--leaf-mib", type=int, default=256)
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--step-iters", type=int, default=110,
                    help="4096x4096 bf16 products a step (110: ~80 ms on a v5e)")
    ap.add_argument("--ahead", type=int, nargs="*", default=[1, 2],
                    help="lookahead depths to run, in leaves beyond the fetched one")
    ap.add_argument("--repeat", type=int, default=1)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    timed = dev.platform != "cpu"
    width = 4096 if timed else 128
    x = jnp.ones((width, width), jnp.bfloat16) * 0.01

    @jax.jit
    def step_fn(x):
        def body(i, a):
            return jnp.tanh(a @ a) * 0.5

        return jax.lax.fori_loop(0, args.step_iters, body, x).sum()

    @jax.jit
    def fresh(a, k):
        return a + k

    elems = args.leaf_mib * (1 << 20) // 4
    base = [jnp.full((elems,), float(i), jnp.float32) for i in range(args.leaves)]
    jax.block_until_ready(base)
    step_fn(x).block_until_ready()
    fresh(base[0], 1.0).block_until_ready()
    print(json.dumps({
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "leaves": args.leaves, "leaf_bytes": elems * 4, "timed": timed,
    }), flush=True)
    k = 2
    for _ in range(args.repeat):
        for name in _cases(args.ahead):
            # Fresh arrays a case: a host copy, once made, stays with its array.
            arrs = [fresh(a, float(k)) for a in base]
            jax.block_until_ready(arrs)
            k += 1
            print(json.dumps(run_case(name, arrs, lambda: step_fn(x), args.steps, timed)),
                  flush=True)
            del arrs
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
