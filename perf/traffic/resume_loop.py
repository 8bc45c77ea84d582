"""Traffic kind ``resume_loop``: restore after a kill, then the first step.

Set-up takes one sync snapshot of the trained state. The window repeats:
zeroed targets, the snapshot's files evicted from the page cache,
``Snapshot.restore``, one train step. Parameter (the traffic mix's file):
``trace_restores``.
"""

from __future__ import annotations

import os
import statistics
import time

import jax

from tpusnap import Snapshot

now = time.monotonic


def _evict(path: str) -> int:
    """Ask the kernel to drop the snapshot's pages: a resume follows a
    kill, on a host that does not hold the snapshot in memory. Returns the
    bytes asked for (a tmpfs ignores the advice)."""
    total = 0
    for root, _, files in os.walk(path):
        for name in files:
            fd = os.open(os.path.join(root, name), os.O_RDONLY)
            try:
                os.fsync(fd)
                os.posix_fadvise(fd, 0, 0, os.POSIX_FADV_DONTNEED)
                total += os.fstat(fd).st_size
            finally:
                os.close(fd)
    return total


def _resume(ctx):
    """One resume: returns the restored state (as restored, before the
    step), the step's loss, and the three times."""
    targets = ctx.app_state(ctx.zeroed_targets())
    jax.block_until_ready(targets["train"].tree)
    _evict(ctx.snapshot_path)
    t_call = now()
    Snapshot(ctx.snapshot_path).restore(targets)
    t_restored = now()
    restored = targets["train"].tree
    _, loss = ctx.train_step(restored, ctx.put_tokens(ctx.next_batch))
    jax.block_until_ready(loss)
    return restored, loss, t_call, t_restored, now()


def setup(ctx) -> None:
    ctx.snapshot_path = os.path.join(ctx.work_dir, "snapshot")
    Snapshot.take(ctx.snapshot_path, ctx.app_state(ctx.state), **ctx.take_kwargs())
    ctx.next_batch = ctx.next_tokens()
    # The uninterrupted loop's next step, which every resume must reproduce.
    _, ctx.loss_uninterrupted = ctx.train_step(ctx.state, ctx.put_tokens(ctx.next_batch))
    ctx.loss_uninterrupted = float(ctx.loss_uninterrupted)
    ctx.saved_state, ctx.state = ctx.state, None
    _resume(ctx)  # warm: the restore path and the targets' program


def run(ctx, seconds: float):
    trace_restores = int(ctx.params["trace_restores"])
    ops, resumes, first_steps, restores, losses = [], [], [], [], []
    failed = 0
    ctx.last_restored = None
    t0 = now()
    deadline = t0 + seconds
    n = 0
    while now() < deadline:
        if n == 1:
            ctx.tracer.start()
        if n == 1 + trace_restores:
            ctx.tracer.stop()
        ctx.last_restored = None  # one state beside the targets, not two
        try:
            state, loss, t_call, t_restored, t_end = _resume(ctx)
        except Exception as e:  # counted, never hidden
            ctx.say("restore_failed", n=n, error=repr(e))
            failed += 1
            n += 1
            continue
        n += 1
        ops.append({"kind": "restore", "t_call": t_call, "t_done": t_end})
        if t_end <= deadline or not resumes:
            resumes.append(t_end - t_call)
            restores.append(t_restored - t_call)
            first_steps.append((t_end - t_restored) * 1e3)
        losses.append(float(loss))
        ctx.last_restored = state
        del state
    ctx.losses = losses
    return {
        "attempted": n,
        "failed": failed,
        "resumes_s": resumes,
        "restores_s": restores,
        "end_to_end": {"resume_s": statistics.median(resumes) if resumes else None},
        "series": {"first_step_ms": first_steps},
        "ops": ops,
    }


def check(ctx, result) -> None:
    """Bytes read back: the state the window's last resume restored is
    compared, bit for bit, with the state the snapshot was taken of; every
    resume's first step gives the uninterrupted loop's loss; the snapshot
    scrubs clean."""
    from perf import harness

    ctx.checks.add(
        "resumed_loss_gap",
        max((abs(x - ctx.loss_uninterrupted) for x in ctx.losses), default=1.0),
        0,
    )
    want = ctx.saved_state
    if ctx.last_restored is None:
        ctx.checks.add("restored_bits_differ", 1, 0)
    else:
        ctx.checks.add(
            "restored_bits_differ", harness.count_mismatches(want, ctx.last_restored), 0
        )
    report = Snapshot(ctx.snapshot_path).verify()
    if not report.clean:
        ctx.say("verify", summary=report.summary())
    ctx.checks.add("verify_unclean", 0 if report.clean else 1, 0)
