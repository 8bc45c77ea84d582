"""Headline benchmark: Snapshot.take throughput to local FS, decomposed.

Mirrors the reference's published benchmark (single-accelerator DDP take
to local FS, /root/reference/benchmarks/ddp/README.md:17 — 20 GB in
~13.91 s ≈ 1.438 GB/s on one A100; DtoH over PCIe is not the bottleneck
there, storage I/O is). ``vs_baseline`` is the throughput ratio against
that 1.438 GB/s.

Besides the headline number the JSON carries a decomposition so the
result is interpretable on any disk:
- ``roofline_gbps``: since round 7, the best in-take probe ceiling
  across the full-scale runs (None if every run's probe failed). The
  16-file in-harness roofline (``measure_roofline``: raw streams
  through the SAME native write engine, same buffer-alignment class,
  same thread pool, zero snapshot machinery) still anchors the tight
  ~2 GB fraction probe below. ``roofline_fraction``
  (take / roofline, median of same-window pairs from the tight ~2 GB
  probe — full-scale pairs span minutes and host contention drifts
  inside them; their fractions are published as a diagnostic list)
  reads directly as pipeline efficiency; ~1.0 means the pipeline adds
  nothing.
- ``roofline_fraction_fullscale`` (since round 7): from IN-TAKE
  INTERLEAVED PROBES — TPUSNAP_PROBE pauses the take's own write
  scheduler once per interval and measures the raw ceiling through the
  same plugin stack, so the full-scale fraction's two sides share
  every disk window (the former separate roofline session spanned
  minutes of drift and scattered 0.206–0.707). Probe time is
  subtracted from the reported take times
  (``probe_overhead_s_runs``); ``roofline_runs_gbps`` now carries the
  per-run probe ceilings.
- The A100 baseline machine's local NVMe sustains multi-GB/s; this VM's
  virtio disk measures ~1-2 GB/s and swings >2x minute to minute
  (single-stream plain-buffered writes are host-throttled to ~0.2 GB/s),
  so the fraction — not the absolute number — is the portable verdict
  on the pipeline.
- ``staging_s`` / ``residual_io_s``: the scheduler's split of the best
  take (staging = the window training would be blocked in async_take).
- ``restore_cold_gbps`` / ``restore_warm_gbps``: full-scale ABSOLUTES —
  fresh-target cold restores and warm-target (production resume-loop)
  restores. No fractions are formed at full scale: a 20 GB sample
  spans minutes and the virtio disk drifts several-fold within that,
  so no two full-scale measurements share a window. The restore
  HEADLINES are ``restore_verified_fraction`` + ``restore_warm_gbps``;
  the cold absolute is a demoted diagnostic (``restore_gbps`` remains
  as a deprecated alias for BENCH_r* trend comparability).
- ``restore_verified_fraction`` — the pipeline-efficiency number,
  from a tight-window ~2 GB probe where each paired sample takes
  seconds: median over rounds of (warm-target restore) /
  (prefaulted+CRC engine reads), both sides measured back-to-back in
  one disk window, neither faulting pages, both checksumming every
  byte. The remaining gap is genuinely the pipeline's. (A
  fresh-target/fresh-buffer "cold" pair was tried and dropped —
  fresh-anon page faulting interacts with drop_caches so erratically
  that adjacent samples disagree 100x.)
  Restore reads land IN PLACE in the target arrays (native fused
  read+checksum, no scratch buffer, no separate verify/copy passes).

- ``incremental_take_s`` / ``incremental_effective_gbps``: an
  ``incremental_from=`` take of the UNCHANGED state against the last
  snapshot — all blobs dedup, so the cost is one fused CRC32C+XXH64
  pass and no storage I/O.
- ``delta_rpo_seconds`` / ``delta_write_amplification`` /
  ``delta_commit_overhead_s``: a short ``Snapshot.stream`` soak over a
  training loop mutating ~1/64 of one array per step — the realized
  steady-state RPO (max interval between micro-commits vs the
  configured cadence), delta bytes written over bytes actually
  mutated, and the per-micro-commit capture cost.
- ``scrub_gbps`` / ``scrub_clean``: ``verify_snapshot`` re-reading and
  checksum-verifying every stored byte — full-scale ABSOLUTES, with
  an engine comparator (``scrub_roofline_gbps``: the exact byte
  ranges the scrub verifies, read through the same native fused
  read+CRC engine at the same concurrency) interleaved for context.
  ``scrub_roofline_fraction`` is the median of same-round pairs from
  the tight ~2 GB probe, like the take and restore fractions.

Run policy: every timed section is preceded by ``os.sync()`` so it
competes only with its own I/O, not earlier sections' writeback. The
restore loop runs one UNTIMED warmup restore first (reported as
``restore_warmup_s``): it absorbs one-time costs — module imports,
native-library load, allocator growth, and the host-side writeback of
the snapshot just taken — that belong to process startup, not the
restore path (r03 measured an 11.9 s first run vs 2.0 s steady-state;
the warmup makes that split explicit instead of folding it into min()).

Memory accounting: ``async_take_peak_rss_mb`` is the peak RSS delta
(rss_profiler, 100 ms sampling) over one async take at bench scale —
the defensive-clone path, where RSS MUST move, so the field doubles as
the sampler's self-check (the former sync-take take_peak_rss_mb was
pinned at ~0 by zero-copy staging and carried no information). Under
PIPELINED staging the delta is bounded by the staging window
(``async_stage_window_gb``), not 1x state; ``async_take_blocked_s`` is
the first-window blocked window, with ``async_blocked_vs_sync_take``
and ``async_breakeven_overlap_s`` the sync/async crossover pair —
together with ``memory_budget_gb`` the evidence for the reference's
signature "adapts to host RAM" property (reference
benchmarks/load_tensor/main.py:39-44).
Set TPUSNAP_BENCH_BYTES to shrink the run below the default
baseline-scale 20 GB.

The state is **host-resident** (numpy): this benchmark measures the
framework pipeline — zero-copy serialization, budget-gated scheduling,
batched storage I/O — on the host alone. It never touches a device, so
none of its numbers is a device number; the device path (train step,
DtoH, take/restore of HBM-resident state) is proven by ``chip_smoke.py``
and is not timed anywhere yet.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
"""

import json
import os
import shutil
import statistics
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

# Reference: 20 GB / 13.91 s on 1×A100, local FS (BASELINE.md).
BASELINE_GBPS = 20.0 / 13.91

# Default = the baseline's own scale (20 GB, reference
# benchmarks/ddp/README.md:17) so vs_baseline compares like with like;
# TPUSNAP_BENCH_BYTES shrinks it for quick local runs.
TOTAL_BYTES = int(os.environ.get("TPUSNAP_BENCH_BYTES", 20 * 1024**3))
N_ARRAYS = 16
N_TAKE_RUNS = int(os.environ.get("TPUSNAP_BENCH_RUNS", 4))


def _drop_caches() -> bool:
    try:
        os.sync()
        with open("/proc/sys/vm/drop_caches", "w") as f:
            f.write("3")
        return True
    except OSError:
        return False


def measure_roofline(tmp: str, nbytes_per_file: int, n_files: int) -> float:
    """Raw aggregate write throughput for the snapshot's exact file
    layout: same native write engine, same 8-worker pool the fs plugin
    uses, same buffer alignment class as user state arrays (numpy
    allocations are not page-aligned), no snapshot machinery on top. This
    is the fastest any checkpoint writer could move these bytes with
    these durability semantics."""
    from tpusnap import _native as native

    # +16 offset: match the alignment class of numpy-owned state arrays
    # so the roofline exercises the same engine the take's writes do.
    buf = native.aligned_empty(nbytes_per_file + 16)[16:]
    # Random payload: constant fill could be flattered by host-side
    # image compression and would not match what the take writes.
    buf[:] = np.random.default_rng(1).integers(
        0, 255, nbytes_per_file, dtype=np.uint8
    )
    best = 0.0
    for _ in range(2):
        os.sync()
        ex = ThreadPoolExecutor(max_workers=8)
        t0 = time.perf_counter()
        list(
            ex.map(
                lambda i: native.write_file(os.path.join(tmp, f"r{i}"), buf),
                range(n_files),
            )
        )
        el = time.perf_counter() - t0
        ex.shutdown()
        for i in range(n_files):
            os.unlink(os.path.join(tmp, f"r{i}"))
        best = max(best, nbytes_per_file * n_files / el / 1e9)
    return best


def main() -> None:
    from tpusnap import PytreeState, Snapshot
    from tpusnap import scheduler as _sched
    from tpusnap import telemetry as _tele

    from tpusnap import _native as _natalloc

    per_array = TOTAL_BYTES // N_ARRAYS
    rng = np.random.default_rng(0)
    # DISTINCT resident buffers (the baseline checkpointed 20 GB of
    # real state; overlapping views would shrink the source working
    # set 16x and flatter every memory-bound pass), built at memcpy
    # speed: one RNG pass generates per_array random u16s, and each
    # array is that block rotated by i elements — pairwise-distinct
    # bytes, no aligned identical blocks for host-side
    # dedup/compression, ~20 s to build at 20 GB where np.roll+RNG per
    # array took ~5 min (THP-advised destinations fault at ~2.4 GB/s
    # vs ~0.17 for 4 KiB pages).
    raw = rng.integers(0, 2**16, per_array // 2, dtype=np.uint16)
    state = {}
    for i in range(N_ARRAYS):
        dst = _natalloc.empty_advised((per_array // 2,), np.uint16)
        dst[: per_array // 2 - i] = raw[i:]
        if i:
            dst[per_array // 2 - i :] = raw[:i]
        state[f"w{i}"] = dst.view(np.float16)
    nbytes = sum(a.nbytes for a in state.values())

    bench_root = tempfile.mkdtemp(prefix="tpusnap_bench_")
    try:
        # Restore first, from a single settled snapshot: the bench writes
        # ~20 GB overall, and the host keeps flushing guest writes for
        # many seconds after the guest's own sync returns — cold reads
        # measured in that window only show the host's writeback, not the
        # restore path.
        restore_snap = os.path.join(bench_root, "restore_src", "snap")
        Snapshot.take(restore_snap, {"model": PytreeState(state)})
        os.sync()
        time.sleep(8.0)

        import glob as _glob

        from tpusnap import _native as _nat

        def _paired_fraction_rounds(snap_path, pstate, rounds=5):
            """Interleaved like-for-like fraction pairs over one
            snapshot (VERDICT r4 #3: best-vs-best across disk windows
            produced unbounded, uninformative fractions). Each round
            measures, back to back in one disk window: prefaulted+CRC
            engine reads, then a warm-target restore — neither faults
            pages, both checksum every byte — whose ratio is
            restore_verified_fraction, the pipeline-efficiency number.
            (A fresh-target/fresh-buffer "cold" pair was tried and
            dropped: fresh-anon page faulting interacts with
            drop_caches so erratically that even adjacent samples
            disagree 100x; the cold restore is reported as an ABSOLUTE
            at full scale instead.) The median over rounds rides out a
            single mid-pair disk stall. Also bit-verifies the last
            warm restore against ``pstate``."""
            files = [
                f
                for f in _glob.glob(
                    os.path.join(snap_path, "**", "*"), recursive=True
                )
                if os.path.isfile(f)
                and not f.endswith(".snapshot_metadata")
                and ".tpusnap" not in f.split(os.sep)
            ]
            sizes = {f: os.path.getsize(f) for f in files}
            total = sum(sizes.values())
            pref = {f: np.empty(sizes[f], dtype=np.uint8) for f in files}
            for buf_ in pref.values():
                buf_[::4096] = 0  # fault every page once

            def engine_read_all(dests, want_crc=False) -> float:
                _drop_caches()

                def read_one(f):
                    n = sizes[f]
                    out = (
                        dests[f] if dests is not None else np.empty(n, np.uint8)
                    )
                    got, _, _ = _nat.read_range_into(
                        f, 0, n, out, want_crc=want_crc
                    )
                    assert got == n

                ex = ThreadPoolExecutor(max_workers=8)
                t0 = time.perf_counter()
                list(ex.map(read_one, files))
                el = time.perf_counter() - t0
                ex.shutdown()
                return total / el / 1e9

            pbytes = sum(a.nbytes for a in pstate.values())
            warm_t = {k: np.zeros_like(v) for k, v in pstate.items()}
            out = {
                "fracs_verified": [],
                "rooflines_verified": [],
                "warm_runs_s": [],
            }
            for _ in range(rounds):
                rl_v = engine_read_all(pref, want_crc=True)
                out["rooflines_verified"].append(rl_v)
                _drop_caches()
                t0 = time.perf_counter()
                Snapshot(snap_path).restore({"model": PytreeState(warm_t)})
                el = time.perf_counter() - t0
                out["warm_runs_s"].append(el)
                out["fracs_verified"].append((pbytes / el / 1e9) / rl_v)
            ks = sorted(pstate)
            out["verified_ok"] = all(
                np.array_equal(
                    warm_t[k].view(np.uint16), pstate[k].view(np.uint16)
                )
                for k in (ks[0], ks[-1])
            )
            return out

        # Untimed warmup restore: absorbs one-time costs (imports, native
        # lib load, allocator growth, residual host writeback of the
        # snapshot written above) so the timed runs measure the restore
        # path, not process startup. Reported, never counted.
        t0 = time.perf_counter()
        Snapshot(restore_snap).restore(
            {
                "model": PytreeState(
                    {f"w{i}": np.empty_like(state[f"w{i}"]) for i in range(N_ARRAYS)}
                )
            }
        )
        restore_warmup_s = time.perf_counter() - t0

        # Full-scale ABSOLUTES: warm-target (production resume-loop) and
        # fresh-target cold restores. No engine rooflines here — at
        # 20 GB a single sample spans minutes and the virtio disk
        # drifts several-fold within that, so no two full-scale
        # measurements share a window; fractions come from the tight
        # 2 GB probe below instead.
        restore_runs = []
        restore_warm_runs = []
        restore_summaries = []
        restore_probe_overheads = []
        warm_target = {
            f"w{i}": np.zeros_like(state[f"w{i}"]) for i in range(N_ARRAYS)
        }
        # In-restore read probes (TPUSNAP_PROBE, the read-lane mirror of
        # the in-take probes below): the cold restore's own scheduler
        # pauses its reads once per interval and measures the raw read
        # ceiling through the same plugin stack, so the summary's
        # restore_roofline_fraction shares every disk window with the
        # reads it judges. Probe cost is subtracted from the reported
        # restore time (restore_probe_overhead_s_runs publishes it).
        from tpusnap.knobs import override_probe as _override_probe_r

        r_probe_interval = max(256 * 1024 * 1024, TOTAL_BYTES // 8)
        r_probe_bytes = min(
            64 * 1024 * 1024, max(8 * 1024 * 1024, r_probe_interval // 8)
        )
        for _ in range(2):
            _drop_caches()
            t0 = time.perf_counter()
            Snapshot(restore_snap).restore({"model": PytreeState(warm_target)})
            restore_warm_runs.append(time.perf_counter() - t0)
            cold = _drop_caches()
            target = {
                f"w{i}": np.empty_like(state[f"w{i}"]) for i in range(N_ARRAYS)
            }
            app_state = {"model": PytreeState(target)}
            with _override_probe_r(
                True, interval_bytes=r_probe_interval, probe_bytes=r_probe_bytes
            ):
                t0 = time.perf_counter()
                Snapshot(restore_snap).restore(app_state)
                el_raw = time.perf_counter() - t0
            summary = _tele.LAST_RESTORE_SUMMARY or {}
            probe_elapsed = (summary.get("probe") or {}).get("elapsed_s") or 0.0
            restore_runs.append(max(el_raw - probe_elapsed, 1e-9))
            restore_probe_overheads.append(probe_elapsed)
            restore_summaries.append(summary)
        best_restore_i = min(
            range(len(restore_runs)), key=restore_runs.__getitem__
        )
        restore_el = restore_runs[best_restore_i]
        restore_gbps = nbytes / restore_el / 1e9
        # Restore-path telemetry of the BEST cold restore — the same
        # phase decomposition the take's stage_breakdown gives, so the
        # restore headline is diagnosable too (plan vs reads vs load).
        best_restore_summary = restore_summaries[best_restore_i] or {}
        restore_stage_breakdown = {
            "phases_s": {
                k: round(v, 3)
                for k, v in (best_restore_summary.get("phases") or {}).items()
            },
            "phase_coverage": best_restore_summary.get("phase_coverage"),
            "counters": {
                k: v
                for k, v in (best_restore_summary.get("counters") or {}).items()
                if not k.startswith("staging_pool.")
            },
        }
        # Bit-pattern comparison: random f16 buffers contain NaNs, and
        # NaN != NaN would fail a value comparison on correct data.
        ok = all(
            np.array_equal(
                app_state["model"].tree[f"w{i}"].view(np.uint16),
                state[f"w{i}"].view(np.uint16),
            )
            for i in (0, N_ARRAYS - 1)
        ) and all(
            np.array_equal(
                warm_target[f"w{i}"].view(np.uint16),
                state[f"w{i}"].view(np.uint16),
            )
            for i in (0, N_ARRAYS - 1)
        )
        del target, app_state, warm_target
        shutil.rmtree(os.path.join(bench_root, "restore_src"), ignore_errors=True)

        # Tight-window FRACTION probe (~2 GB: every sample is seconds,
        # so the paired samples genuinely share a disk window).
        def _build_probe_state():
            """Distinct-offset views into the random block (pairwise
            distinct bytes; probes only feed the fraction pairs, so
            the overlapping source footprint is fine here); lengths
            equalized and offsets clamped so the smallest TOTAL_BYTES
            still fits. ONE definition so the take and restore
            fraction probes can never desynchronize their scales."""
            per = min(TOTAL_BYTES, 2 * 1024**3) // N_ARRAYS
            plen = per // 2 - N_ARRAYS
            step = max(
                1, min(997, (len(raw) - plen) // max(N_ARRAYS - 1, 1))
            )
            return {
                f"w{i}": raw[i * step : i * step + plen].view(np.float16)
                for i in range(N_ARRAYS)
            }

        probe_state = _build_probe_state()
        probe_snap = os.path.join(bench_root, "fprobe", "snap")
        Snapshot.take(probe_snap, {"model": PytreeState(probe_state)})
        os.sync()
        fr = _paired_fraction_rounds(probe_snap, probe_state, rounds=5)
        ok = ok and fr["verified_ok"]
        shutil.rmtree(os.path.join(bench_root, "fprobe"), ignore_errors=True)
        restore_verified_fracs = fr["fracs_verified"]
        restore_rooflines_verified = fr["rooflines_verified"]

        # Full-scale fractions come from IN-TAKE INTERLEAVED PROBES
        # (TPUSNAP_PROBE): the take's own write scheduler pauses its I/O
        # once per interval and measures the raw engine ceiling through
        # the same plugin stack, seconds (not minutes) from the writes
        # it judges. This replaces the former separate roofline session
        # per run — at 20 GB that pair spanned minutes of drifting
        # virtio bandwidth and scattered the fraction 0.206–0.707
        # (ROADMAP 5a); the probe and the take now genuinely share
        # every disk window. Probe cost (~8 probes x PROBE_BYTES) is
        # subtracted from the reported take time (probe_overhead_s_runs
        # publishes what was subtracted).
        from tpusnap.knobs import override_probe
        from tpusnap.rss_profiler import measure_rss_deltas

        probe_interval = max(256 * 1024 * 1024, TOTAL_BYTES // 8)
        probe_bytes = min(64 * 1024 * 1024, max(8 * 1024 * 1024, probe_interval // 8))
        times = []
        splits = []
        rooflines = []
        take_fracs = []
        take_summaries = []
        probe_overheads = []
        budget_bytes = None
        for run in range(N_TAKE_RUNS):
            tmp = os.path.join(bench_root, f"take{run}")
            app_state = {"model": PytreeState(state)}
            # Drain pending page-cache writeback from earlier iterations so
            # each timed take competes only with its own I/O.
            os.sync()
            with override_probe(
                True, interval_bytes=probe_interval, probe_bytes=probe_bytes
            ):
                t0 = time.perf_counter()
                Snapshot.take(os.path.join(tmp, "snap"), app_state)
                el_raw = time.perf_counter() - t0
            summary = _tele.LAST_TAKE_SUMMARY or {}
            probe_info = summary.get("probe") or {}
            probe_elapsed = probe_info.get("elapsed_s") or 0.0
            el = max(el_raw - probe_elapsed, 1e-9)
            times.append(el)
            probe_overheads.append(probe_elapsed)
            # Runs whose probes failed (the runner stands down after
            # one failure) contribute None — kept IN the per-run lists
            # so cold_run_index keeps indexing every *_runs array, but
            # EXCLUDED from the aggregates (a 0.0 would read as a
            # catastrophic regression in roofline_gbps/..._fullscale
            # and the bench history event, when only the probe
            # hiccuped).
            ceiling = probe_info.get("write_gbps_p50")
            rooflines.append(ceiling)
            # The summary's own fraction: payload throughput over the
            # non-probe wall against the in-take ceiling.
            frac = summary.get("roofline_fraction")
            if frac is None and ceiling:
                frac = (nbytes / el / 1e9) / ceiling
            take_fracs.append(frac)
            stats = _sched.LAST_EXECUTION_STATS.get("write", {})
            budget_bytes = stats.get("budget_bytes") or budget_bytes
            splits.append(
                (stats.get("staging_s"), stats.get("total_s"))
            )
            take_summaries.append(summary)
            if run + 1 < N_TAKE_RUNS:
                shutil.rmtree(tmp, ignore_errors=True)
        best_i = min(range(len(times)), key=times.__getitem__)
        best = times[best_i]
        gbps = nbytes / best / 1e9
        staging_s, sched_total_s = splits[best_i]
        # None (not 0.0) when every run's probe failed: absent beats a
        # fake regression in the JSON and the history gate.
        roofline = max((r for r in rooflines if r), default=None)
        # Per-stage telemetry of the BEST take (tpusnap.telemetry): the
        # phase decomposition that makes the headline number diagnosable
        # — where the wall-clock went, not just how long it was.
        best_summary = take_summaries[best_i] or {}
        stage_breakdown = {
            "phases_s": {
                k: round(v, 3)
                for k, v in (best_summary.get("phases") or {}).items()
            },
            "phase_coverage": best_summary.get("phase_coverage"),
            "counters": {
                k: v
                for k, v in (best_summary.get("counters") or {}).items()
                if not k.startswith("staging_pool.")
            },
            "budget_high_water_gb": (
                round(
                    best_summary["gauges"]["scheduler.budget_used_bytes"] / 1e9, 2
                )
                if "scheduler.budget_used_bytes"
                in (best_summary.get("gauges") or {})
                else None
            ),
        }

        # Async-take leg at bench scale: the blocked window — under
        # PIPELINED staging this is the first-window clone pass, not the
        # full-state clone — and its peak RSS (bounded by the staging
        # window, not 1x state). The leg replaces the former sync-take
        # take_peak_rss_mb, which was pinned at ~0 by design (sync
        # takes of numpy state stage zero-copy views) and therefore
        # indistinguishable from a broken sampler — the async clone
        # path is the configuration where RSS MUST move, so the field
        # doubles as the sampler's self-check.
        #
        # Two takes: COLD (pool empty — the first window's clones pay
        # first-touch faulting; later windows already recycle the
        # buffers earlier writes released) and WARM (the steady-state
        # checkpoint loop: even window 0 reuses the previous take's
        # parked pages). The default 4 GiB pool covers the 2 GiB
        # default window with room — windowed staging is what made the
        # old state-sized pool override unnecessary.
        from tpusnap.knobs import get_async_stage_window_bytes

        try:
            async_blocked = []
            async_total = []
            rss_deltas = []
            for run in range(2):
                async_dir = os.path.join(
                    bench_root, f"async_take{run}", "snap"
                )
                os.sync()
                t0 = time.perf_counter()
                with measure_rss_deltas(rss_deltas):
                    pending = Snapshot.async_take(
                        async_dir, {"model": PytreeState(state)}
                    )
                    async_blocked.append(time.perf_counter() - t0)
                    pending.wait()
                async_total.append(time.perf_counter() - t0)
                shutil.rmtree(
                    os.path.dirname(async_dir), ignore_errors=True
                )
            async_peak_rss = max(rss_deltas, default=0)
            async_window_bytes = get_async_stage_window_bytes() or 0
        finally:
            from tpusnap import _staging_pool as _sp

            _sp.clear()  # release the window-sized pool

        # Beyond-reference capabilities, measured on the last snapshot:
        # an incremental take of the UNCHANGED state (all blobs dedup —
        # cost is one CRC pass, no storage I/O) and a full integrity
        # scrub (every stored byte re-read and verified).
        from tpusnap import verify_snapshot

        last_snap = os.path.join(
            bench_root, f"take{N_TAKE_RUNS - 1}", "snap"
        )
        # The incremental base records 64-bit dedup hashes
        # (TPUSNAP_RECORD_DEDUP_HASHES — the documented pattern for
        # bases of planned chains): skip decisions need 64-bit evidence
        # on both sides, and a plain base conservatively rewrites once.
        # Taken untimed so the headline take samples stay hash-lane-free.
        from tpusnap.knobs import override_record_dedup_hashes

        inc_base = os.path.join(bench_root, "inc_base", "snap")
        with override_record_dedup_hashes(True):
            Snapshot.take(inc_base, {"model": PytreeState(state)})
        os.sync()
        inc_path = os.path.join(bench_root, "inc", "snap")
        t0 = time.perf_counter()
        Snapshot.take(
            inc_path, {"model": PytreeState(state)}, incremental_from=inc_base
        )
        inc_take_s = time.perf_counter() - t0
        shutil.rmtree(os.path.join(bench_root, "inc_base"), ignore_errors=True)
        shutil.rmtree(os.path.join(bench_root, "inc"), ignore_errors=True)

        # Delta-mode section (tpusnap.delta): a short stream over a
        # "training loop" mutating ~1/64 of one array per step. Records
        # the steady-state realized RPO (max commit interval), delta
        # write amplification (delta bytes / changed bytes) and
        # per-micro-commit overhead — the numbers `history --check
        # --kind bench` regression-gates for the streaming mode.
        from tpusnap import slo as _slo_mod

        delta_root = os.path.join(bench_root, "delta_stream")
        d_state = {"model": PytreeState({"w0": state["w0"]})}
        d_arr = state["w0"].view(np.uint16)
        rows = d_arr.shape[0]
        delta_cadence_s = 0.5
        changed_bytes_total = 0
        stream = Snapshot.stream(
            delta_root, d_state, cadence_s=delta_cadence_s
        )
        t0 = time.perf_counter()
        step = 0
        while time.perf_counter() - t0 < 6.0:
            lo = (step * rows // 64) % rows
            hi = min(lo + rows // 64, rows)
            d_arr[lo:hi] ^= 1
            changed_bytes_total += d_arr[lo:hi].nbytes
            stream.mark_step(bytes_changed=int(d_arr[lo:hi].nbytes))
            step += 1
            time.sleep(0.01)
        stream.close(final_commit=False)
        delta_stats = dict(stream.stats)
        delta_rpo_s = _slo_mod.tracker().rpo_s()
        delta_write_amp = (
            delta_stats["bytes_written_total"] / changed_bytes_total
            if changed_bytes_total
            else None
        )
        shutil.rmtree(delta_root, ignore_errors=True)

        # Compression section (tpusnap.compress): compressed vs bypass
        # effective GB/s on a DETERMINISTIC bandwidth-constrained path —
        # the chaos plugin's write-path token bucket pins the pipe at
        # compress_throttle_gbps, the regime (cloud, virtio, tiered
        # remote drain) the codec exists for — plus the auto policy's
        # decision on both pipes: the throttled take must compress, the
        # local-fs take must bypass with wall within noise of
        # compression=off. State is bf16-precision f32 (mixed-precision
        # export shape): random u16 mantissa-truncated, so the shuffle
        # filter sees real entropy in the exponent planes and zeros in
        # the dropped ones — not an all-zeros softball.
        from tpusnap import compress as _comp_mod
        from tpusnap.knobs import override_compress

        c_rng = np.random.default_rng(7)
        c_arr = c_rng.standard_normal((192 << 20) // 4).astype(np.float32)
        c_arr = (c_arr.view(np.uint32) & np.uint32(0xFFFF0000)).view(
            np.float32
        )
        comp_nbytes = c_arr.nbytes
        comp_bw_gbps = 0.15
        comp_spec = f"transient_per_op=0,bandwidth_gbps={comp_bw_gbps}"
        comp_root = os.path.join(bench_root, "compress")

        def _comp_take(leg, mode, chaos):
            path = os.path.join(comp_root, leg, "snap")
            url = f"chaos+file://{path}" if chaos else path
            opts = {"fault_plan": comp_spec} if chaos else None
            with override_compress(mode=mode):
                t0 = time.perf_counter()
                Snapshot.take(
                    url,
                    {"model": PytreeState({"w": c_arr})},
                    storage_options=opts,
                )
                el = time.perf_counter() - t0
            stored = sum(
                os.path.getsize(os.path.join(r, f))
                for r, _, fs in os.walk(path)
                for f in fs
                if not f.endswith(".snapshot_metadata")
                and ".tpusnap" not in r.split(os.sep)
            )
            decision = _comp_mod.LAST_DECISION
            shutil.rmtree(os.path.join(comp_root, leg), ignore_errors=True)
            return el, stored, decision

        comp_off_s, _, _ = _comp_take("off", "off", chaos=True)
        comp_on_s, comp_stored, _ = _comp_take("on", "on", chaos=True)
        # Auto on the throttled pipe: a fresh ceiling registry forces
        # the policy mini-probe THROUGH the throttled plugin stack, so
        # the decision comes from a live measurement of this pipe (the
        # full-scale takes above already fed the registry the REAL
        # local-fs ceiling under the same innermost label).
        _comp_mod._reset_ceilings()
        comp_auto_s, _, comp_auto_dec = _comp_take("auto", "auto", chaos=True)
        _comp_mod._reset_ceilings()
        # Local fs, auto-vs-off: best-of-3 per side (192 MiB local
        # takes are sub-second; a single sample's page-cache/writeback
        # jitter exceeds the 5% acceptance band being measured).
        local_auto_runs, local_off_runs = [], []
        local_auto_dec = None
        for _ in range(3):
            el, _, d = _comp_take("lauto", "auto", chaos=False)
            local_auto_runs.append(el)
            local_auto_dec = d
            el, _, _ = _comp_take("loff", "off", chaos=False)
            local_off_runs.append(el)
        shutil.rmtree(comp_root, ignore_errors=True)
        compress_section = {
            # The codec's rate on the throttled auto take's own sample.
            "compress_codec_gbps": (
                round(comp_auto_dec.sample_gbps, 3) if comp_auto_dec else None
            ),
            "compress_throttle_gbps": comp_bw_gbps,
            "compress_section_gb": round(comp_nbytes / 1024**3, 2),
            "compress_ratio": round(comp_nbytes / comp_stored, 3),
            "compress_effective_gbps": round(
                comp_nbytes / comp_on_s / 1e9, 3
            ),
            "compress_bypass_gbps": round(comp_nbytes / comp_off_s / 1e9, 3),
            # The headline: effective throughput multiplier from
            # compressing on the bandwidth-bound path (acceptance:
            # >= 1.5x for this bf16/f32 state).
            "compress_vs_bypass": round(comp_off_s / comp_on_s, 3),
            "compress_auto_throttled_s": round(comp_auto_s, 2),
            "compress_auto_decision_throttled": (
                comp_auto_dec.to_meta()["decision"] if comp_auto_dec else None
            ),
            "compress_auto_reason_throttled": (
                comp_auto_dec.reason if comp_auto_dec else None
            ),
            "compress_auto_decision_local": (
                local_auto_dec.to_meta()["decision"] if local_auto_dec else None
            ),
            "compress_auto_reason_local": (
                local_auto_dec.reason if local_auto_dec else None
            ),
            "compress_auto_local_wall_s": round(min(local_auto_runs), 3),
            "compress_off_local_wall_s": round(min(local_off_runs), 3),
            # Acceptance: <= 1.05 — auto's bypass decision costs ~no
            # wall on a pipe that outruns the codec.
            "compress_auto_local_overhead": round(
                min(local_auto_runs) / min(local_off_runs), 3
            ),
        }

        # Scrub, interleaved with its own roofline: the exact byte ranges
        # the scrub verifies, read through the same native fused read+CRC
        # engine at the same concurrency, zero manifest/asyncio machinery.
        # r03 published a single scrub sample with no roofline and the
        # driver caught it 9x low (0.347 vs 3.0 GB/s) — competing with the
        # writeback of the take that preceded it; the sync + interleaved
        # sampling below makes the number self-verifying.
        from tpusnap.inspect import iter_blobs, load_snapshot_metadata
        from tpusnap.knobs import get_scrub_concurrency

        os.sync()
        # Settle: the guest's sync returns before the HOST finishes
        # absorbing the take section's writeback; cold reads in that
        # window measure the host's flush, not the scrub (same reason
        # the restore section runs first from a settled snapshot).
        time.sleep(8.0)
        def _scrub_ranges_of(snap_path):
            manifest = load_snapshot_metadata(snap_path).manifest
            ranges = []  # (abs_path, offset, nbytes)
            for b in iter_blobs(manifest):
                off, end = b.byte_range if b.byte_range else (0, None)
                if end is None:
                    end = os.path.getsize(
                        os.path.join(snap_path, b.location)
                    )
                ranges.append(
                    (os.path.join(snap_path, b.location), off, end - off)
                )
            return ranges

        def _scrub_roofline_once(ranges) -> float:
            _drop_caches()
            n_slots = get_scrub_concurrency()
            scratch = max(n for _, _, n in ranges)
            total = sum(n for _, _, n in ranges)
            local = __import__("threading").local()

            def read_one(rng):
                path_, off_, n_ = rng
                buf = getattr(local, "buf", None)
                if buf is None or buf.nbytes < n_:
                    buf = _nat.aligned_empty(max(n_, scratch))
                    local.buf = buf
                got, _, _ = _nat.read_range_into(
                    path_, off_, n_, memoryview(buf)[:n_], want_crc=True
                )
                assert got == n_

            ex = ThreadPoolExecutor(max_workers=n_slots)
            t0 = time.perf_counter()
            list(ex.map(read_one, ranges))
            el = time.perf_counter() - t0
            ex.shutdown()
            return total / el / 1e9

        scrub_ranges = _scrub_ranges_of(last_snap)
        scrub_bytes = sum(n for _, _, n in scrub_ranges)
        scrub_runs = []
        scrub_rooflines = []
        scrub_fullscale_fracs = []
        scrub_clean = True
        for _ in range(2):
            rl_fs = _scrub_roofline_once(scrub_ranges)
            scrub_rooflines.append(rl_fs)
            _drop_caches()
            t0 = time.perf_counter()
            scrub_report = verify_snapshot(last_snap)
            el_fs = time.perf_counter() - t0
            scrub_runs.append(el_fs)
            scrub_fullscale_fracs.append(
                (scrub_bytes / el_fs / 1e9) / rl_fs
            )
            scrub_clean = scrub_clean and scrub_report.clean
        scrub_s = min(scrub_runs)
        scrub_roofline = max(scrub_rooflines)

        # ---- tight-window fraction probe: take + scrub ----
        # Same reasoning as the restore fractions: at full scale a
        # single sample spans minutes and host contention drifts
        # several-fold within a pair, so the FRACTIONS come from ~2 GB
        # samples that take seconds; the full-scale runs above are the
        # absolutes (their per-run fractions are published as a
        # diagnostic list).
        fprobe_dir = os.path.join(bench_root, "take_fprobe")
        os.makedirs(fprobe_dir, exist_ok=True)
        tp_state = _build_probe_state()
        tp_file_bytes = next(iter(tp_state.values())).nbytes
        tp_nbytes = sum(a.nbytes for a in tp_state.values())
        take_probe_fracs = []
        tp_snap = None
        for r in range(5):
            rl = measure_roofline(fprobe_dir, tp_file_bytes, N_ARRAYS)
            tp_snap = os.path.join(fprobe_dir, f"t{r}", "snap")
            os.sync()
            t0 = time.perf_counter()
            Snapshot.take(tp_snap, {"model": PytreeState(tp_state)})
            el = time.perf_counter() - t0
            take_probe_fracs.append((tp_nbytes / el / 1e9) / rl)
            if r + 1 < 5:
                shutil.rmtree(os.path.dirname(tp_snap), ignore_errors=True)
        os.sync()
        time.sleep(4.0)
        tp_ranges = _scrub_ranges_of(tp_snap)
        tp_bytes = sum(n for _, _, n in tp_ranges)
        scrub_probe_fracs = []
        for _ in range(3):
            rl = _scrub_roofline_once(tp_ranges)
            _drop_caches()
            t0 = time.perf_counter()
            rep = verify_snapshot(tp_snap)
            el = time.perf_counter() - t0
            scrub_clean = scrub_clean and rep.clean
            scrub_probe_fracs.append((tp_bytes / el / 1e9) / rl)
        shutil.rmtree(fprobe_dir, ignore_errors=True)
    finally:
        shutil.rmtree(bench_root, ignore_errors=True)

    # Warm-only views of the full-scale run arrays: run 0 is the COLD
    # run of its section (first take at full scale faults/evicts the
    # page-cache working set the later runs inherit — r05's 0.206
    # first-run outlier in roofline_fraction_fullscale_runs), so trend
    # tooling should read the warm aggregates and treat runs[cold_run_index]
    # as warmup, not regression. The cross-run history applies the same
    # rule via its cold tag.
    def _warm(vals):
        return vals[1:] if len(vals) > 1 else vals

    # Aggregation views of the per-run fraction list: None entries are
    # failed-probe runs (kept in the *_runs arrays for index alignment
    # with cold_run_index, excluded from every aggregate).
    _fracs_valid = [f for f in take_fracs if f is not None]
    _warm_fracs_valid = [f for f in _warm(take_fracs) if f is not None]

    result = {
        "metric": "snapshot_take_local_fs",
        "value": round(gbps, 3),
        "unit": "GB/s",
        "vs_baseline": round(gbps / BASELINE_GBPS, 3),
        "roofline_gbps": round(roofline, 3) if roofline else None,
        # Median of same-round take/roofline pairs from the
        # tight ~2 GB probe (seconds per sample, so the pair
        # genuinely shares a host/disk window; full-scale
        # pairs span minutes and drift several-fold — their
        # fractions are published below as a diagnostic).
        "roofline_fraction": round(
            statistics.median(take_probe_fracs), 3
        ),
        "roofline_fraction_probe_gb": round(
            min(TOTAL_BYTES, 2 * 1024**3) / 1024**3, 2
        ),
        "roofline_fraction_runs": [
            round(f, 3) for f in take_probe_fracs
        ],
        # Full-scale fractions from IN-TAKE INTERLEAVED PROBES
        # (TPUSNAP_PROBE through the take's own scheduler): each
        # take self-measures its engine ceiling seconds from the
        # writes it judges, so the fraction is immune to the
        # multi-minute disk drift that made the former separate
        # roofline session scatter 0.206–0.707 (see
        # BENCHMARKS.md "Round 7 protocol change").
        "roofline_fullscale_source": "intake_probes",
        # Failed-probe runs publish null at their index (every *_runs
        # array stays aligned with take_runs_s and cold_run_index) and
        # are excluded from the aggregates.
        "roofline_fraction_fullscale": (
            round(statistics.median(_fracs_valid), 3)
            if _fracs_valid
            else None
        ),
        "roofline_fraction_fullscale_runs": [
            round(f, 3) if f is not None else None for f in take_fracs
        ],
        "probe_write_gbps_runs": [
            round(r, 3) if r is not None else None for r in rooflines
        ],
        "probe_overhead_s_runs": [
            round(p, 2) for p in probe_overheads
        ],
        "probe_interval_gb": round(probe_interval / 1024**3, 2),
        "probe_bytes_mb": round(probe_bytes / 1024**2, 1),
        # Index of the cold-cache run in every *_runs array of
        # this JSON (the section's first run), plus warm-only
        # aggregates so trend tooling doesn't flag warmup.
        "cold_run_index": 0,
        "roofline_fraction_fullscale_warm": (
            round(statistics.median(_warm_fracs_valid), 3)
            if _warm_fracs_valid
            else None
        ),
        # Since round 7 these are the in-take probe ceilings (the
        # name kept for BENCH_r01-r06 trend comparability; null at a
        # failed-probe run's index).
        "roofline_runs_gbps": [
            round(r, 3) if r is not None else None for r in rooflines
        ],
        "take_runs_s": [round(t, 2) for t in times],
        "take_warm_best_s": round(min(_warm(times)), 2),
        "stage_breakdown": stage_breakdown,
        "staging_s": round(staging_s, 2) if staging_s else None,
        "residual_io_s": (
            round(sched_total_s - staging_s, 2)
            if staging_s and sched_total_s
            else None
        ),
        # RESTORE HEADLINES are the verified-fraction pair below:
        # the fraction (pipeline efficiency, like-for-like paired
        # samples) and the warm absolute (the production
        # resume-loop). The cold absolute was demoted to
        # restore_cold_gbps (ROADMAP 5d): a 20 GB cold sample
        # spans minutes of drifting virtio bandwidth and page-cache
        # state, so it reads as a disk-weather report, not a
        # pipeline verdict.
        "restore_verified_fraction": round(
            statistics.median(restore_verified_fracs), 3
        ),
        "restore_warm_gbps": round(
            nbytes / min(restore_warm_runs) / 1e9, 3
        ),
        "restore_cold_gbps": round(restore_gbps, 3),
        # Deprecated alias of restore_cold_gbps, kept so BENCH_r01-r05
        # trend tooling and the cross-run history stay comparable.
        "restore_gbps": round(restore_gbps, 3),
        "restore_verified_fraction_runs": [
            round(f, 3) for f in restore_verified_fracs
        ],
        "restore_roofline_verified_runs_gbps": [
            round(r, 3) for r in restore_rooflines_verified
        ],
        "restore_runs_s": [round(t, 2) for t in restore_runs],
        # Drift-immune read-path fraction of the BEST cold restore:
        # payload read throughput over the non-probe wall against the
        # in-restore probe ceiling (same window, same plugin stack).
        # None when the probe failed or stood down.
        "restore_roofline_fraction": best_restore_summary.get(
            "restore_roofline_fraction"
        ),
        "restore_probe_read_gbps": (
            best_restore_summary.get("probe") or {}
        ).get("read_gbps_p50"),
        "restore_probe_overhead_s_runs": [
            round(o, 3) for o in restore_probe_overheads
        ],
        "restore_stage_breakdown": restore_stage_breakdown,
        "restore_warm_runs_s": [
            round(t, 2) for t in restore_warm_runs
        ],
        "restore_warmup_s": round(restore_warmup_s, 2),
        "restore_cold_cache": cold,
        "restore_verified": ok,
        # Warm = the steady-state checkpoint loop (pool pages
        # reused); cold = first take of the process. Under pipelined
        # staging the blocked window is O(stage window), not O(state).
        "async_take_blocked_s": round(async_blocked[-1], 2),
        "async_take_blocked_cold_s": round(async_blocked[0], 2),
        "async_take_total_s": round(async_total[-1], 2),
        "async_stage_window_gb": round(async_window_bytes / 1e9, 2),
        # Sync/async crossover, both sides from this run: the blocked
        # window is blocked_vs_sync of a sync take (training-visible
        # cost ratio), and async is the net win whenever the training
        # work overlapped with the background drain exceeds
        # breakeven_overlap_s (the drain's wall-clock excess over a
        # sync take). See BENCHMARKS.md "Sync/async crossover".
        "async_blocked_vs_sync_take": round(
            async_blocked[-1] / min(_warm(times)), 4
        ),
        "async_breakeven_overlap_s": round(
            max(async_total[-1] - min(_warm(times)), 0.0), 2
        ),
        # Clone-path RSS: must be >> 0 (the windowed clones are real
        # allocations) but BOUNDED by the staging window — no longer
        # ~1x state; still the RSS sampler's self-check, unlike the
        # sync take whose zero-copy staging pinned the old
        # take_peak_rss_mb at 0.
        "async_take_peak_rss_mb": round(async_peak_rss / 1e6),
        "memory_budget_gb": (
            round(budget_bytes / 1e9, 2) if budget_bytes else None
        ),
        "incremental_take_s": round(inc_take_s, 2),
        "incremental_effective_gbps": round(
            nbytes / inc_take_s / 1e9, 3
        ),
        # Delta streaming mode (tpusnap.delta): realized RPO in the
        # steady state (max interval between micro-commits — the
        # headline the stream exists to shrink; configured cadence
        # alongside for the ratio), write amplification (delta bytes
        # written / bytes actually mutated; tile-grain dedup keeps it
        # ~1), and per-micro-commit overhead (the dual-hash pass +
        # changed-tile writes).
        "delta_cadence_s": delta_cadence_s,
        "delta_commits": delta_stats["commits"],
        "delta_rpo_seconds": delta_stats["max_commit_interval_s"],
        "delta_rpo_at_close_s": round(delta_rpo_s, 3),
        "delta_write_amplification": (
            round(delta_write_amp, 3) if delta_write_amp else None
        ),
        "delta_commit_overhead_s": delta_stats["last_commit_wall_s"],
        "delta_bytes_written": delta_stats["bytes_written_total"],
        "delta_compactions": delta_stats["compactions"],
        "scrub_s": round(scrub_s, 2),
        "scrub_gbps": round(scrub_bytes / scrub_s / 1e9, 3),
        "scrub_roofline_gbps": round(scrub_roofline, 3),
        # Median of same-round pairs from the tight probe.
        "scrub_roofline_fraction": round(
            statistics.median(scrub_probe_fracs), 3
        ),
        "scrub_roofline_fraction_runs": [
            round(f, 3) for f in scrub_probe_fracs
        ],
        "scrub_roofline_fraction_fullscale_runs": [
            round(f, 3) for f in scrub_fullscale_fracs
        ],
        "scrub_roofline_fraction_fullscale_warm": round(
            statistics.median(_warm(scrub_fullscale_fracs)), 3
        ),
        "scrub_runs_gbps": [
            round(scrub_bytes / t / 1e9, 3) for t in scrub_runs
        ],
        "scrub_roofline_runs_gbps": [
            round(r, 3) for r in scrub_rooflines
        ],
        "scrub_clean": scrub_clean,
        # Fused tile compression (tpusnap.compress): measured on its own
        # bf16-precision state over a deterministic token-bucket pipe —
        # see "Compression section" above for leg semantics.
        **compress_section,
    }

    # Checkpoint-SLO accuracy check (tpusnap.slo), free with every bench
    # run: the RTO estimator grades itself against the restore this very
    # run measured (the bench's own takes/restores fed history and the
    # tracker's commit anchor above), and the realized commit interval
    # rides along — `history --kind bench` then trends estimator drift.
    try:
        from tpusnap import slo as _slo

        _est = _slo.estimate_rto(nbytes)
        _slo_state = _slo.tracker().snapshot_state()
        result["slo_estimated_rto_s"] = _est.seconds if _est.ok else None
        result["slo_rto_actual_s"] = round(restore_el, 3)
        result["slo_rto_ratio"] = (
            round(_est.seconds / restore_el, 3)
            if _est.ok and restore_el > 0
            else None
        )
        result["slo_commit_interval_s"] = _slo_state.get("commit_interval_s")
    except Exception:
        pass

    # Record the headline trajectory into the same cross-run history the
    # takes/restores above already fed (kind="take"/"restore", first run
    # cold-tagged automatically) — BENCH_r*.json trajectories become
    # queryable by `python -m tpusnap history --kind bench [--check]`.
    try:
        from tpusnap import history as _hist

        # Tail-latency gate feed: p99/p50 storage-write latency of the
        # best take's log2 histograms (event_from_summary derives the
        # same fields take events carry, so `history --check --kind
        # bench --metric storage_write_p99_s` gates like-for-like).
        _hist_fields = _hist.event_from_summary("bench", best_summary or {})
        # Read-path trend feed from the best cold restore's summary:
        # storage_read_p50_s/p99_s gate tail read latency upward and
        # restore_roofline_fraction/probe_read_gbps trend the read-lane
        # pipeline efficiency, like-for-like with restore events.
        _hist_restore = _hist.event_from_summary(
            "bench", best_restore_summary or {}
        )
        _hist.record_event(
            {
                "v": 1,
                "ts": round(time.time(), 3),
                "kind": "bench",
                "rank": 0,
                "world_size": 1,
                "bytes": nbytes,
                "wall_s": round(best, 3),
                "throughput_gbps": round(gbps, 3),
                **{
                    k: _hist_fields[k]
                    for k in (
                        "storage_write_p50_s",
                        "storage_write_p99_s",
                        "probe_write_gbps",
                    )
                    if k in _hist_fields
                },
                "roofline_fraction": result["roofline_fraction"],
                "roofline_fraction_fullscale": result[
                    "roofline_fraction_fullscale"
                ],
                "roofline_fraction_fullscale_warm": result[
                    "roofline_fraction_fullscale_warm"
                ],
                "restore_gbps": result["restore_gbps"],
                "restore_verified_fraction": result[
                    "restore_verified_fraction"
                ],
                **{
                    k: _hist_restore[k]
                    for k in (
                        "storage_read_p50_s",
                        "storage_read_p99_s",
                        "restore_roofline_fraction",
                        "probe_read_gbps",
                    )
                    if k in _hist_restore
                },
                "async_take_blocked_s": result["async_take_blocked_s"],
                "async_take_peak_rss_mb": result["async_take_peak_rss_mb"],
                "scrub_gbps": result["scrub_gbps"],
                "incremental_effective_gbps": result[
                    "incremental_effective_gbps"
                ],
                # Streaming-mode regression feed: `history --check
                # --kind bench --metric delta_rpo_seconds` gates the
                # realized RPO upward like any duration, and the
                # amplification/overhead columns trend alongside.
                **{
                    k: result[k]
                    for k in (
                        "delta_rpo_seconds",
                        "delta_write_amplification",
                        "delta_commit_overhead_s",
                    )
                    if result.get(k) is not None
                },
                # Compression regression feed: `history --check --kind
                # bench --metric compress_effective_gbps` gates the
                # bandwidth-bound win downward like every throughput,
                # and the recorded auto decisions make a policy flip
                # (compress where it should bypass, or vice versa)
                # visible in the trend without rereading BENCH JSONs.
                **{
                    k: result[k]
                    for k in (
                        "compress_effective_gbps",
                        "compress_bypass_gbps",
                        "compress_vs_bypass",
                        "compress_ratio",
                        "compress_codec_gbps",
                        "compress_auto_decision_throttled",
                        "compress_auto_decision_local",
                        "compress_auto_local_overhead",
                    )
                    if result.get(k) is not None
                },
                # Estimator-vs-measured: slo_rto_ratio near 1.0 means
                # the RTO gauge can be trusted; `history --check --kind
                # bench --metric slo_rto_actual_s` gates restore time
                # upward like every other duration.
                **{
                    k: result[k]
                    for k in (
                        "slo_estimated_rto_s",
                        "slo_rto_actual_s",
                        "slo_rto_ratio",
                        "slo_commit_interval_s",
                    )
                    if result.get(k) is not None
                },
            }
        )
    except Exception:
        pass

    print(json.dumps(result))


if __name__ == "__main__":
    sys.exit(main())
