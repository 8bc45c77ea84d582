#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that tpusnap still starts on the chip.

One process drives the library's main path once, through the entry points
a user calls: a trainer takes steps with its state in HBM
(``tpusnap.models``: ``TransformerConfig()`` at its declared full width),
the state goes out through ``Snapshot.take`` / ``Snapshot.async_take`` and
comes back through ``Snapshot.restore``, bit-exact, with the loss
continuing. A four-chip leg (ring attention over a 1x2x2 mesh, restore
under a second mesh shape) runs in the same process when four TPU devices
are visible, and is reported as *not run* otherwise.

    python chip_smoke.py              # needs a TPU; exits non-zero without one
    python chip_smoke.py --rehearsal  # tiny widths, any backend; proves nothing
                                      # about a chip and never says "ok"

Failing is the point. Any failed check raises and the process exits
non-zero; nothing is caught and reported beside exit code 0. Any record a
``tpusnap`` logger emits at WARNING or above during the run — the
library's quiet fallbacks all log there — fails the run too.

The last line of standard output is one JSON object with exactly these
keys: ``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
The line before it, ``... summary: {...}``, carries what was observed
(per-leg results, ``flash_compiled``, ``native_built_here``, bytes moved,
the compile cache). Durations are printed as observations only; none of
them is a metric.
"""

from __future__ import annotations

import argparse
import collections
import json
import logging
import os
import sys
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tpusnap import (
    MetricsSink,
    PytreeState,
    Snapshot,
    _native,
    compile_cache,
    metrics_sink,
    telemetry,
)
from tpusnap.host_offload import is_host_resident, to_host_offload
from tpusnap.models import Transformer, TransformerConfig, make_mesh, make_train_step
from tpusnap.models.transformer import (
    init_train_state,
    random_tokens,
    train_state_shardings,
)
from tpusnap.ops.flash_attention import _attention_reference, flash_attention
from tpusnap.test_utils import check_state_dict_eq
from tpusnap.transforms import cast_on_save

# bf16 kernel output against an f32 "highest"-precision reference: half
# a bf16 ulp is 2^-7 ≈ 0.008 at the largest outputs (|x| < 4) and the
# inputs are bf16 as well; 2e-2 is ~2.5 ulp there. Softmax statistics in
# a narrower type, a wrong scale or a wrong mask miss it by 10x or more.
FLASH_ATOL = 2e-2
# f32 → bf16 → f32 round trip: round-to-nearest loses at most 2^-9
# relative; one extra bit of slack. fp8 or int8 storage would fail it.
CAST_RTOL = 2.0**-8


class SmokeFailure(RuntimeError):
    """A check of the smoke did not hold."""


def check(condition: bool, message: str) -> None:
    if not condition:
        raise SmokeFailure(message)


class Reporter:
    """Prints result lines that name the device they came from."""

    def __init__(self, devices: Sequence[Any], rehearsal: bool) -> None:
        d = devices[0]
        self.device = {
            "platform": d.platform,
            "kind": d.device_kind,
            "count": len(devices),
        }
        self._prefix = (
            f"{'rehearsal ' if rehearsal else ''}[platform: {d.platform}, "
            f"device_kind: {d.device_kind}, devices: {len(devices)}, "
            f"jax: {jax.__version__}]"
        )

    def __call__(self, label: str, **fields: Any) -> None:
        print(
            f"{self._prefix} {label}: {json.dumps(fields, sort_keys=True)}",
            flush=True,
        )


class WarningTrap(logging.Handler):
    """Collects every ``tpusnap`` log record at WARNING or above: the
    library's fallbacks (host re-pack, failed native build, failed DtoH
    prefetch, unanswerable aliasing probe) all announce themselves there."""

    def __init__(self) -> None:
        super().__init__(level=logging.WARNING)
        self.messages: List[str] = []

    def emit(self, record: logging.LogRecord) -> None:
        line = f"{record.levelname} {record.name}: {record.getMessage()}"
        self.messages.append(line)
        print(f"chip_smoke: tpusnap logged {line}", file=sys.stderr, flush=True)


class Observer(MetricsSink):
    """Sums what one take or restore moved, from the spans and counters
    the library already records."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.dtoh_span_bytes = 0
        self.counters: collections.Counter = collections.Counter()

    def on_span(self, name, duration_s, attrs) -> None:
        if name == "dtoh":
            with self._lock:
                self.dtoh_span_bytes += int(attrs.get("bytes", 0))

    def on_counter(self, name, delta, value) -> None:
        with self._lock:
            self.counters[name] += delta

    def moved(self, state_bytes: int) -> Dict[str, Any]:
        enqueued = self.counters["dtoh.enqueued_bytes"]
        return {
            "state_bytes": state_bytes,
            "dtoh_span_bytes": self.dtoh_span_bytes,
            "dtoh_enqueued_bytes": enqueued,
            "dtoh_bytes_per_state_byte": round(
                (self.dtoh_span_bytes + enqueued) / state_bytes, 3
            ),
            "storage_bytes_written": self.counters["storage.bytes_written"],
            "storage_bytes_read": self.counters["storage.bytes_read"],
        }


def tiny_config(mesh_shape: Tuple[int, int, int], use_ring: bool):
    """The rehearsal/dryrun preset: every sharded dim a small multiple
    of its mesh axis. Never used on the chip."""
    dp, fs, tp = mesh_shape
    return TransformerConfig(
        vocab_size=128 * fs * tp,
        d_model=32 * tp * max(fs, 1),
        n_heads=2 * tp,
        n_layers=2,
        d_ff=64 * tp * fs,
        max_seq_len=16 * fs,
        n_experts=2 * dp,
        use_ring_attention=use_ring,
    )


def _memory_stat(devices, key: str) -> Optional[List[int]]:
    stats = [d.memory_stats() for d in devices]
    if any(s is None for s in stats):
        return None  # the CPU backend keeps no allocator statistics
    return [int(s[key]) for s in stats]


def _assert_restored(want_tree, got_tree, target_tree, platform: str) -> None:
    """Every restored leaf is a jax.Array on ``platform`` with its
    target's sharding, and the tree is bit-equal to what was saved."""
    for got, target in zip(jax.tree.leaves(got_tree), jax.tree.leaves(target_tree)):
        check(isinstance(got, jax.Array), f"restored a {type(got).__name__}")
        check(
            {d.platform for d in got.devices()} == {platform},
            f"restored onto {got.devices()}, expected {platform}",
        )
        check(
            got.sharding.is_equivalent_to(target.sharding, got.ndim),
            f"restored sharding {got.sharding} != target's {target.sharding}",
        )
    check(
        check_state_dict_eq(want_tree, got_tree),
        "restored state is not bit-equal to the saved state",
    )


def run_leg(
    say: Reporter,
    cfg,
    mesh,
    work_dir: str,
    *,
    batch: int,
    expect_flash: bool,
    second_mesh_shape: Optional[Tuple[int, int, int]] = None,
) -> Dict[str, Any]:
    """Train, take, async-take under training, verify, restore, resume,
    incremental take, reduced-precision take — on ``mesh``, at ``cfg``.

    The one body behind both legs of the smoke, the rehearsal and
    ``__graft_entry__.dryrun_multichip``."""
    devices = list(mesh.devices.flat)
    platform = devices[0].platform
    out: Dict[str, Any] = {"mesh": dict(mesh.shape), "batch": batch}
    model = Transformer(cfg)

    # 1. A few train steps from a seeded state built in place.
    in_use_before = _memory_stat(devices, "bytes_in_use")
    state = init_train_state(model, mesh, jax.random.PRNGKey(0))
    train_step = make_train_step(model, mesh)
    tokens = random_tokens(cfg, mesh, np.random.default_rng(0), batch)
    t0 = time.monotonic()
    lowered = train_step.lower(state, tokens).as_text()
    out["flash_compiled"] = "tpu_custom_call" in lowered
    if expect_flash:
        check(
            out["flash_compiled"],
            'attention_impl="auto" did not lower to the Mosaic kernel '
            "(no tpu_custom_call in the train step)",
        )
    losses = []
    for _ in range(3):
        state, loss = train_step(state, tokens)
        losses.append(float(loss))
    out["losses"] = losses
    check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    say(
        "train",
        losses=losses,
        flash_compiled=out["flash_compiled"],
        first_steps_s=round(time.monotonic() - t0, 2),
    )

    # The state sits where param_specs() says, spread over the mesh.
    leaves = jax.tree.leaves(state)
    for leaf, want in zip(leaves, jax.tree.leaves(train_state_shardings(model, mesh))):
        check(
            leaf.sharding.is_equivalent_to(want, leaf.ndim),
            f"state leaf sharded {leaf.sharding}, param_specs() says {want}",
        )
    state_bytes = sum(leaf.nbytes for leaf in leaves)
    in_use_after = _memory_stat(devices, "bytes_in_use")
    if in_use_after is None:
        out["bytes_in_use_per_device"] = "not reported by this backend"
    else:
        grown = [a - b for a, b in zip(in_use_after, in_use_before)]
        out["bytes_in_use_per_device"] = grown
        check(
            max(grown) <= 1.5 * sum(grown) / len(grown),
            f"state is piled on one device, not spread: {grown}",
        )
    say(
        "state",
        state_bytes=state_bytes,
        n_leaves=len(leaves),
        bytes_in_use_per_device=out["bytes_in_use_per_device"],
    )

    def app_state(tree):
        return {"train": PytreeState(tree)}

    # 2. Sync take of the device-resident state.
    sync_path = os.path.join(work_dir, "sync")
    with metrics_sink(Observer()) as seen:
        t0 = time.monotonic()
        Snapshot.take(sync_path, app_state(state))
        take_s = time.monotonic() - t0
    out["take"] = seen.moved(state_bytes)
    say("take", take_s=round(take_s, 2), **out["take"])

    # 3. Async take; training goes on while it drains.
    async_path = os.path.join(work_dir, "async")
    with metrics_sink(Observer()) as seen:
        pending = Snapshot.async_take(async_path, app_state(state))
        state_a, loss_a = train_step(state, tokens)
        state_b, loss_b = train_step(state_a, tokens)
        jax.block_until_ready(loss_b)
        drained_before_wait = pending.done()
        pending.wait()
    out["async_take"] = seen.moved(state_bytes)
    out["async_take"]["drained_before_wait"] = drained_before_wait
    say("async_take", **out["async_take"])

    # 4. Both snapshots scrub clean.
    for path in (sync_path, async_path):
        report = Snapshot(path).verify()
        check(report.clean, f"{path}: {report.summary()}")

    # 5. Restore into zeroed targets: same bytes, same placement, and the
    # next step reproduces the uninterrupted run's loss.
    target = jax.tree.map(jnp.zeros_like, state)
    restored = PytreeState(target)
    with metrics_sink(Observer()) as seen:
        Snapshot(async_path).restore({"train": restored})
    _assert_restored(state, restored.tree, target, platform)
    _, loss_resumed = train_step(restored.tree, tokens)
    check(
        float(loss_resumed) == float(loss_a),
        f"resumed loss {float(loss_resumed)!r} != uninterrupted {float(loss_a)!r}",
    )
    out["restore"] = {
        "storage_bytes_read": seen.counters["storage.bytes_read"],
        "resumed_loss": float(loss_resumed),
        "uninterrupted_loss": float(loss_a),
    }
    say("restore", bit_exact=True, **out["restore"])

    # 6. Incremental take layered on the first snapshot, then a bf16
    # cast_on_save take restored upcast. Both run device code at stage
    # time (the slab pack; the per-leaf cast).
    incr_path = os.path.join(work_dir, "incremental")
    Snapshot.take(incr_path, app_state(state_b), incremental_from=sync_path)
    report = Snapshot(incr_path).verify()
    check(report.clean, f"{incr_path}: {report.summary()}")
    target = jax.tree.map(jnp.zeros_like, state_b)
    restored = PytreeState(target)
    Snapshot(incr_path).restore({"train": restored})
    _assert_restored(state_b, restored.tree, target, platform)

    cast_path = os.path.join(work_dir, "bf16")
    Snapshot.take(
        cast_path,
        app_state(state_b),
        _custom_array_prepare_func=cast_on_save({"train/params/**": jnp.bfloat16}),
    )
    restored = PytreeState(jax.tree.map(jnp.zeros_like, state_b))
    Snapshot(cast_path).restore({"train": restored})
    for want, got in zip(jax.tree.leaves(state_b), jax.tree.leaves(restored.tree)):
        check(got.dtype == want.dtype, f"upcast restored {got.dtype}, not {want.dtype}")
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=CAST_RTOL, atol=1e-30
        )
    say("incremental_and_cast", incremental_clean=True, cast_rtol=CAST_RTOL)

    # The overlap-region reshard: the same snapshot into another mesh.
    if second_mesh_shape is not None:
        mesh2 = make_mesh(devices, second_mesh_shape)
        target = init_train_state(model, mesh2, jax.random.PRNGKey(1))
        restored = PytreeState(target)
        Snapshot(sync_path).restore({"train": restored})
        _assert_restored(state, restored.tree, target, platform)
        out["resharded_restore"] = {"mesh": dict(mesh2.shape), "bit_exact": True}
        say("resharded_restore", **out["resharded_restore"])
    # Process-lifetime high-water mark (a later leg includes the earlier).
    out["peak_bytes_in_use_per_device"] = _memory_stat(devices, "peak_bytes_in_use")
    return out


def check_flash_kernel(say: Reporter, shape, *, interpret: bool) -> Dict[str, Any]:
    """The Pallas kernel alone against the f32 reference, bf16 inputs."""
    q, k, v = (
        jax.random.normal(key, shape, jnp.bfloat16)
        for key in jax.random.split(jax.random.PRNGKey(1), 3)
    )
    out = jax.jit(
        lambda q, k, v: flash_attention(q, k, v, causal=True, interpret=interpret)
    )(q, k, v)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(
            lambda q, k, v: _attention_reference(
                *(t.astype(jnp.float32) for t in (q, k, v)), True
            )
        )(q, k, v)
    out = np.asarray(out, np.float32)
    check(out.shape == tuple(shape), f"kernel output shape {out.shape}")
    check(bool(np.isfinite(out).all()), "kernel output is not finite")
    err = float(np.abs(out - np.asarray(ref)).max())
    check(err <= FLASH_ATOL, f"flash kernel off by {err} > {FLASH_ATOL}")
    result = {
        "shape": list(shape),
        "interpret": interpret,
        "max_abs_err": round(err, 6),
        "atol": FLASH_ATOL,
    }
    say("flash_kernel_vs_reference", **result)
    return result


def check_pinned_host(say: Reporter, device, work_dir: str) -> Dict[str, Any]:
    """A ``pinned_host`` array survives take/restore as ``pinned_host``."""
    n = 1 << 20  # 4 MB of f32
    source = jax.device_put(jnp.arange(n, dtype=jnp.float32), device)
    offloaded = to_host_offload(source)
    check(is_host_resident(offloaded), "to_host_offload left the array on device")
    path = os.path.join(work_dir, "pinned_host")
    Snapshot.take(path, {"m": PytreeState({"table": offloaded})})
    zeros = to_host_offload(jax.device_put(jnp.zeros(n, jnp.float32), device))
    target = PytreeState({"table": zeros})
    Snapshot(path).restore({"m": target})
    restored = target.tree["table"]
    kind = restored.sharding.memory_kind
    check(kind == "pinned_host", f"restored memory kind {kind!r}, not 'pinned_host'")
    check(
        np.array_equal(np.asarray(restored), np.asarray(source)),
        "pinned_host round trip changed values",
    )
    result = {"restored_memory_kind": kind, "values_equal": True}
    say("pinned_host", **result)
    return result


def _cache_entries(cache_dir: str) -> int:
    return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0


def main(argv: Optional[Sequence[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--rehearsal",
        action="store_true",
        help="tiny widths on whatever backend JAX finds (the CPU here): "
        "debugs this script, proves nothing about a chip, never prints ok",
    )
    args = parser.parse_args(argv)

    all_devices = jax.devices()
    platform = all_devices[0].platform
    if platform != "tpu" and not args.rehearsal:
        print(
            f"chip_smoke: JAX found platform {platform!r} "
            f"({all_devices[0].device_kind} x{len(all_devices)}), not a TPU. "
            "This script never continues on another backend.",
            file=sys.stderr,
        )
        return 2

    trap = WarningTrap()
    logging.getLogger("tpusnap").addHandler(trap)
    cache_dir = compile_cache.enable()
    cache_events: collections.Counter = collections.Counter()
    jax.monitoring.register_event_listener(
        lambda event, **_: cache_events.update([event])
    )
    cache_before = _cache_entries(cache_dir)

    say = Reporter(all_devices, args.rehearsal)
    legs: Dict[str, Any] = {}
    t_start = time.monotonic()
    with tempfile.TemporaryDirectory(prefix="tpusnap_chip_smoke_") as work_dir:
        one = all_devices[:1]
        if args.rehearsal:
            cfg, batch = tiny_config((1, 1, 1), use_ring=False), 2
            kernel_shape, interpret = (1, 128, 2, 64), platform != "tpu"
        else:
            cfg, batch = TransformerConfig(), 8
            kernel_shape = (2, cfg.max_seq_len, cfg.n_heads, cfg.head_dim)
            interpret = False
        legs["one_chip"] = run_leg(
            say,
            cfg,
            make_mesh(one, (1, 1, 1)),
            os.path.join(work_dir, "one_chip"),
            batch=batch,
            expect_flash=platform == "tpu",
        )
        legs["one_chip"]["flash_kernel"] = check_flash_kernel(
            say, kernel_shape, interpret=interpret
        )
        legs["one_chip"]["pinned_host"] = check_pinned_host(say, one[0], work_dir)

        if len(all_devices) >= 4:
            four = all_devices[:4]
            mesh_shape = (1, 2, 2)
            if args.rehearsal:
                cfg = tiny_config(mesh_shape, use_ring=True)
            else:
                cfg = TransformerConfig(n_experts=4, use_ring_attention=True)
            legs["four_chip"] = run_leg(
                say,
                cfg,
                make_mesh(four, mesh_shape),
                os.path.join(work_dir, "four_chip"),
                batch=batch,
                expect_flash=False,  # this leg takes the ring path
                second_mesh_shape=(2, 1, 2),
            )
        else:
            legs["four_chip"] = (
                f"not run: {len(all_devices)} {platform} device(s) visible, "
                "the leg needs 4"
            )
            say("four_chip", status=legs["four_chip"])

    native = _native.build_info()
    fallbacks = telemetry.counter_value("batcher.device_pack_fallbacks")
    summary = {
        "flash_compiled": legs["one_chip"]["flash_compiled"],
        "native_loaded": native["loaded"],
        # The loader only opens the file whose name carries the hash of
        # (source, flags, this host's CPU features); built_this_run says
        # whether this very process compiled it.
        "native_built_here": native["path"] == _native.library_path(),
        "native_built_this_run": native["built_in_process"],
        "device_pack_fallbacks": fallbacks,
        "tpusnap_warnings": trap.messages,
        "legs": legs,
        "compile_cache": {
            "dir": cache_dir,
            "from_env": bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
            "entries_before": cache_before,
            "entries_after": _cache_entries(cache_dir),
            "hits": cache_events["/jax/compilation_cache/cache_hits"],
            "misses": cache_events["/jax/compilation_cache/cache_misses"],
        },
        "wall_s": round(time.monotonic() - t_start, 1),
    }
    check(native["loaded"], "the native engine did not load; the smoke requires it")
    check(summary["native_built_here"], f"native engine loaded from {native['path']}")
    check(fallbacks == 0, f"{fallbacks} device slab pack(s) fell back to the host")
    check(not trap.messages, f"tpusnap logged at WARNING or above: {trap.messages}")

    say("summary", **summary)
    if args.rehearsal:
        # Deliberately no "ok" key: a rehearsal says nothing about the chip.
        verdict = {"rehearsal": True, "chip": "not run", "device": say.device}
    else:
        verdict = {"ok": True, "device": say.device}
    print(json.dumps(verdict), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
