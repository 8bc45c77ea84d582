"""Traffic kind ``save_loop``: async saves under a live training loop.

Parameters (the traffic mix's file): ``first_save_s`` and ``save_every_s``
(the k-th save starts at the first step boundary at or after
``first_save_s + k * save_every_s`` into the window, so every window
holds the same number of saves, however fast its steps are), and for the
traced slice ``trace_lead_s`` and ``trace_max_s``. One take at a time: a
save still draining when the next is due is waited for, and the wait is its
stall. A save's time to durable ends when its take does (``_watch``), not
at the step boundary where the loop learns of it.

Under a program whose step donates its state (``ctx.donates``) the loop
does what such a trainer must: ``pending.wait_staged()`` between
``async_take`` and the next step, because that step deletes the arrays the
take was handed. The wait lies inside that step's time, so inside the
save's stall and the rate; its length is ``staged_wait_ms``. No state is
read after ``train_step`` has had it: what the check compares the restored
state with are fingerprints of every leaf's bits, taken on the device
before the take (a device copy would be the state a second time, and by
the compiler's sizes the step of the one configuration that donates leaves
no room for it).
"""

from __future__ import annotations

import os
import statistics
import threading
import time

import jax

from tpusnap import Snapshot

now = time.monotonic

# The harness refuses a donating program under a kind that does not say this.
SERVES_A_DONATING_STEP = True


def _dirty_kb():
    """Page-cache bytes not yet written back when a save starts: a backlog
    here means the saves come faster than the storage takes them."""
    try:
        with open("/proc/meminfo") as f:
            return next(int(ln.split()[1]) for ln in f if ln.startswith("Dirty:"))
    except (OSError, StopIteration):
        return None


def _take(ctx, n: int):
    path = os.path.join(ctx.work_dir, f"save_{n}")
    return path, Snapshot.async_take(path, ctx.app_state(ctx.state), **ctx.take_kwargs())


def _donates(ctx) -> bool:
    return getattr(ctx, "donates", False)


def _kept_for_the_check(ctx):
    """What ``restored_bits_differ`` compares with: the state handed to the
    take, which stays alive where nothing is donated; the fingerprints of
    its leaves where the next step deletes it (waited for, so that the
    take's own clock starts on an idle device: their pass over the state
    lies inside the save's stall and outside its blocked window)."""
    if _donates(ctx):
        return jax.block_until_ready(ctx.fingerprints(ctx.state))
    return ctx.state


def _wait_staged(pending) -> float:
    """Seconds a donating trainer waits before the step that deletes what
    the take was handed."""
    t = now()
    pending.wait_staged()
    return now() - t


def _watch(save, pending) -> threading.Thread:
    """Stamps ``save`` at the moment its take is durable, from a thread that
    does nothing but wait for it: the loop itself looks once a step, and
    would read every save up to one step late."""

    def body():
        try:
            pending.wait()
        except Exception:
            return  # the loop's own wait() meets it and counts the save failed
        save["t_durable"] = now()

    watcher = threading.Thread(target=body, name="perf-durable-watch", daemon=True)
    watcher.start()
    return watcher


def setup(ctx) -> None:
    """One whole take under two steps, so that the slab-pack programs and
    the native library are built before the window; under a donating
    step the fingerprints' program too."""
    if _donates(ctx):
        _kept_for_the_check(ctx)
    path, pending = _take(ctx, 0)
    if _donates(ctx):
        _wait_staged(pending)
    for _ in range(2):
        ctx.state, loss = ctx.train_step(ctx.state, ctx.put_tokens(ctx.next_tokens()))
        jax.block_until_ready(loss)
    pending.wait()
    ctx.remove_later(path)


def run(ctx, seconds: float):
    p = ctx.params
    every, first = float(p["save_every_s"]), float(p["first_save_s"])
    lead, trace_max_s = float(p["trace_lead_s"]), float(p["trace_max_s"])
    tokens_per_step = ctx.batch * ctx.seq_len
    steps = []  # (start, end, a take was pending or started)
    saves = []
    pending = save = fresh = None
    ctx.held = None  # the newest durable snapshot's path, state and next step
    n_saves = 0
    i = 0
    t0 = now()
    deadline = t0 + seconds
    next_save_at = t0 + first
    while True:
        if ctx.tracer.state == "idle" and now() >= next_save_at - lead:
            ctx.tracer.start()
        if ctx.tracer.state == "tracing" and (
            now() - ctx.tracer.t_start > trace_max_s
            or (saves and now() > saves[0].get("t_durable", deadline) + lead)
        ):
            ctx.tracer.stop()
        t_start = now()
        if t_start >= deadline and pending is None:
            break
        busy = pending is not None
        t_begin = t_start
        if pending is not None and t_start >= next_save_at:
            # As a trainer does: one take at a time. The wait is stall, and
            # the stall of the save waited for: the next one begins after it.
            try:
                pending.wait()
                t_begin = now()
                _durable(ctx, save, i, t_begin)
            except Exception as e:
                ctx.say("save_failed", step=save["step"], error=repr(e))
                save["failed"] = True
            pending = None
        if next_save_at <= t_start and now() < deadline:
            next_save_at += every
            if ctx.held is not None:
                ctx.held["state"] = None  # one saved state alive beside the loop's, not two
            n_saves += 1
            save = fresh = {"step": i, "t_begin": t_begin, "state": _kept_for_the_check(ctx),
                            "dirty_kb": _dirty_kb()}
            save["t_call"] = now()
            try:
                save["path"], pending = _take(ctx, n_saves)
                save["watcher"] = _watch(save, pending)
                if _donates(ctx):
                    save["staged_wait_s"] = _wait_staged(pending)
            except Exception as e:  # counted, never hidden
                ctx.say("save_failed", step=i, error=repr(e))
                save["failed"] = True
                pending = None
            save["t_returned"] = now()
            saves.append(save)
            busy = True
        batch = ctx.next_tokens()
        ctx.state, loss = ctx.train_step(ctx.state, ctx.put_tokens(batch))
        jax.block_until_ready(loss)
        t_end = now()
        if fresh is not None:  # the step the saved state went into
            fresh["tokens"], fresh["loss_after"] = batch, loss
            fresh = None
        steps.append((t_start, t_end, busy))
        i += 1
        if pending is not None and pending.done():
            try:
                pending.wait()
                _durable(ctx, save, i, t_end)
            except Exception as e:
                ctx.say("save_failed", step=save["step"], error=repr(e))
                save["failed"] = True
            pending = None

    in_window = [s for s in steps if s[1] <= deadline]
    quiet = [(e - s) * 1e3 for s, e, b in in_window if not b]
    quiet_ms = statistics.median(quiet) if quiet else None
    durable, stalls = [], []
    for sv in saves:
        if sv.get("failed") or "t_durable" not in sv:
            continue
        durable.append(sv["t_durable"] - sv["t_call"])
        if quiet_ms is not None:
            n = sv["end_step"] - sv["step"]
            stalls.append((sv["t_end"] - sv["t_begin"]) * 1e3 - n * quiet_ms)
    failed = sum(1 for sv in saves if sv.get("failed") or "t_durable" not in sv)
    staged = [sv["staged_wait_s"] * 1e3 for sv in saves if "staged_wait_s" in sv]
    ops = [
        {"kind": "save", "t_call": sv["t_begin"], "t_done": sv.get("t_end", now())}
        for sv in saves
    ]
    return {
        "attempted": len(saves),
        "failed": failed,
        "steps_in_window": len(in_window),
        "saves_durable": len(durable),
        "stalls_ms": stalls,
        "durable_s": durable,
        "durable_seen_late_ms": [(sv["t_seen"] - sv["t_durable"]) * 1e3 for sv in saves
                                 if "t_seen" in sv],
        "dirty_kb_at_save": [sv.get("dirty_kb") for sv in saves],
        **({"staged_wait_ms": staged} if staged else {}),
        "end_to_end": {
            "train_tokens_per_s": len(in_window) * tokens_per_step / seconds,
            "save_stall_ms": statistics.median(stalls) if stalls else None,
            "save_durable_s": statistics.median(durable) if durable else None,
            **({"staged_wait_ms": statistics.median(staged)} if staged else {}),
        },
        "series": {
            "step_ms": [(e - s) * 1e3 for s, e, _ in in_window],
            "quiet_step_ms": quiet,
        },
        "ops": ops,
    }


def _durable(ctx, save, step: int, t_end: float) -> None:
    """The loop has seen that the take of ``save`` is durable (its watcher
    has stamped when it became so): keep what the check needs of the newest
    one, and drop the one before it."""
    save.pop("watcher").join()
    save["t_seen"] = now()
    save["end_step"] = step
    save["t_end"] = t_end
    if ctx.held is not None:
        ctx.remove_later(ctx.held["path"])
    ctx.held = {k: save.pop(k) for k in ("state", "tokens", "loss_after")}
    ctx.held["path"] = save["path"]


def check(ctx, result) -> None:
    """Bytes read back: the newest snapshot the window made durable is
    restored into zeroed targets and compared, bit for bit, with the state
    the loop handed to that take; the snapshot scrubs clean; and the step
    after the restore gives the loss the uninterrupted loop got there."""
    from perf import harness

    held = ctx.held
    if held is None:
        ctx.checks.add("saves_durable_missing", 1, 0)
        return
    ctx.state = None  # room for the targets beside the saved state
    targets = ctx.app_state(ctx.zeroed_targets())
    Snapshot(held["path"]).restore(targets)
    restored = targets["train"].tree
    if _donates(ctx):  # leaves whose fingerprints differ; else elements whose bits do
        differ = harness.count_fingerprint_mismatches(ctx, held["state"], restored)
    else:
        differ = harness.count_mismatches(held["state"], restored)
    ctx.checks.add("restored_bits_differ", differ, 0)
    report = Snapshot(held["path"]).verify()
    if not report.clean:
        ctx.say("verify", summary=report.summary())
    ctx.checks.add("verify_unclean", 0 if report.clean else 1, 0)
    held["state"] = None
    _, loss = ctx.train_step(restored, ctx.put_tokens(held["tokens"]))
    ctx.checks.add(
        "resumed_loss_gap", abs(float(loss) - float(held["loss_after"])), 0
    )
