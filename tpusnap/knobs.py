"""Tunable knobs, each an env var with a context-manager override for tests.

TPU-native counterpart of the reference's knob system
(/root/reference/torchsnapshot/knobs.py:21-96). Defaults match the
reference: 512MB max chunk, 512MB max shard, 128MB slab threshold.
"""

import contextlib
import logging
import os
import threading
from typing import Dict, Generator, Optional

logger = logging.getLogger(__name__)

_MAX_CHUNK_SIZE_ENV_VAR = "TPUSNAP_MAX_CHUNK_SIZE_BYTES"
_MAX_SHARD_SIZE_ENV_VAR = "TPUSNAP_MAX_SHARD_SIZE_BYTES"
_SLAB_SIZE_THRESHOLD_ENV_VAR = "TPUSNAP_SLAB_SIZE_THRESHOLD_BYTES"
_DISABLE_BATCHING_ENV_VAR = "TPUSNAP_DISABLE_BATCHING"
_DISABLE_DEVICE_BATCHING_ENV_VAR = "TPUSNAP_DISABLE_DEVICE_BATCHING"
_DISABLE_PARTITIONER_ENV_VAR = "TPUSNAP_DISABLE_PARTITIONER"
_MEMORY_BUDGET_ENV_VAR = "TPUSNAP_MAX_PER_RANK_MEMORY_BUDGET_BYTES"
_DISABLE_NATIVE_ENV_VAR = "TPUSNAP_DISABLE_NATIVE"
_DISABLE_DIRECT_IO_ENV_VAR = "TPUSNAP_DISABLE_DIRECT_IO"
_DISABLE_DONTCACHE_ENV_VAR = "TPUSNAP_DISABLE_DONTCACHE"
_DISABLE_CHECKSUM_ENV_VAR = "TPUSNAP_DISABLE_CHECKSUM"
_DIRECT_IO_QD_ENV_VAR = "TPUSNAP_DIRECT_IO_QD"
_DIRECT_IO_CHUNK_ENV_VAR = "TPUSNAP_DIRECT_IO_CHUNK_BYTES"
_TILE_CHECKSUM_ENV_VAR = "TPUSNAP_TILE_CHECKSUM_BYTES"
_SCRUB_CONCURRENCY_ENV_VAR = "TPUSNAP_SCRUB_CONCURRENCY"
_RECORD_DEDUP_HASHES_ENV_VAR = "TPUSNAP_RECORD_DEDUP_HASHES"
_DURABLE_COMMIT_ENV_VAR = "TPUSNAP_DURABLE_COMMIT"
_TELEMETRY_ENV_VAR = "TPUSNAP_TELEMETRY"
_DISABLE_JOURNAL_ENV_VAR = "TPUSNAP_DISABLE_JOURNAL"
_STALL_DEADLINE_ENV_VAR = "TPUSNAP_STALL_DEADLINE_S"
_HEARTBEAT_INTERVAL_ENV_VAR = "TPUSNAP_HEARTBEAT_INTERVAL_S"
_TELEMETRY_DIR_ENV_VAR = "TPUSNAP_TELEMETRY_DIR"
_METRICS_EXPORT_ENV_VAR = "TPUSNAP_METRICS_EXPORT"
_METRICS_DIR_ENV_VAR = "TPUSNAP_METRICS_DIR"
_HISTORY_ENV_VAR = "TPUSNAP_HISTORY"
_HISTORY_MAX_BYTES_ENV_VAR = "TPUSNAP_HISTORY_MAX_BYTES"
_STAGE_THREADS_ENV_VAR = "TPUSNAP_STAGE_THREADS"
_ASYNC_STAGE_WINDOW_ENV_VAR = "TPUSNAP_ASYNC_STAGE_WINDOW_BYTES"
_ASYNC_COW_ENV_VAR = "TPUSNAP_ASYNC_COW"
_PROBE_ENV_VAR = "TPUSNAP_PROBE"
_PROBE_INTERVAL_ENV_VAR = "TPUSNAP_PROBE_INTERVAL_BYTES"
_PROBE_BYTES_ENV_VAR = "TPUSNAP_PROBE_BYTES"
_AUTOTUNE_ENV_VAR = "TPUSNAP_AUTOTUNE"
_STAGING_POOL_ENV_VAR = "TPUSNAP_STAGING_POOL_BYTES"
_LOCKCHECK_ENV_VAR = "TPUSNAP_LOCKCHECK"
_FLIGHT_ENV_VAR = "TPUSNAP_FLIGHT"
_FLIGHT_RING_ENV_VAR = "TPUSNAP_FLIGHT_RING"
_FLIGHT_FLUSH_ENV_VAR = "TPUSNAP_FLIGHT_FLUSH_S"
_SLO_RPO_ENV_VAR = "TPUSNAP_SLO_RPO_S"
_SLO_RTO_ENV_VAR = "TPUSNAP_SLO_RTO_S"
_SLO_STREAM_CADENCE_X_ENV_VAR = "TPUSNAP_SLO_STREAM_CADENCE_X"
_DELTA_CADENCE_ENV_VAR = "TPUSNAP_DELTA_CADENCE_S"
_DELTA_MAX_CHAIN_ENV_VAR = "TPUSNAP_DELTA_MAX_CHAIN"
_TIER_DRAIN_ENV_VAR = "TPUSNAP_TIER_DRAIN"
_TIER_OP_DEADLINE_ENV_VAR = "TPUSNAP_TIER_OP_DEADLINE_S"
_TIER_OUTAGE_THRESHOLD_ENV_VAR = "TPUSNAP_TIER_OUTAGE_THRESHOLD"
_TIER_BACKOFF_CAP_ENV_VAR = "TPUSNAP_TIER_BACKOFF_CAP_S"
_TIER_LOCAL_RETENTION_ENV_VAR = "TPUSNAP_TIER_LOCAL_RETENTION_S"
_COMPRESS_ENV_VAR = "TPUSNAP_COMPRESS"
_COMPRESS_MIN_BLOB_ENV_VAR = "TPUSNAP_COMPRESS_MIN_BLOB_BYTES"
_BARRIER_TIMEOUT_ENV_VAR = "TPUSNAP_BARRIER_TIMEOUT_S"
_LIVENESS_TTL_ENV_VAR = "TPUSNAP_LIVENESS_TTL_S"
_RANK_FAILURE_ENV_VAR = "TPUSNAP_RANK_FAILURE"
_JOB_ID_ENV_VAR = "TPUSNAP_JOB_ID"
_FLEET_DIR_ENV_VAR = "TPUSNAP_FLEET_DIR"
_CAS_DIR_ENV_VAR = "TPUSNAP_CAS_DIR"
_CAS_GRACE_ENV_VAR = "TPUSNAP_CAS_GRACE_S"
_CAS_LEASE_TTL_ENV_VAR = "TPUSNAP_CAS_LEASE_TTL_S"
_CAS_REMOTE_ENV_VAR = "TPUSNAP_CAS_REMOTE"
_ACCESS_LEDGER_ENV_VAR = "TPUSNAP_ACCESS_LEDGER"
_ACCESS_LEDGER_MAX_BYTES_ENV_VAR = "TPUSNAP_ACCESS_LEDGER_MAX_BYTES"

_DEFAULT_MAX_CHUNK_SIZE_BYTES = 512 * 1024 * 1024
_DEFAULT_MAX_SHARD_SIZE_BYTES = 512 * 1024 * 1024
_DEFAULT_SLAB_SIZE_THRESHOLD_BYTES = 128 * 1024 * 1024
# Per-file O_DIRECT write queue depth / chunk size (measured on virtio:
# QD 2 x 32 MiB out-runs single-in-flight 8 MiB by ~30% aggregate).
_DEFAULT_DIRECT_IO_QD = 2
_DEFAULT_DIRECT_IO_CHUNK_BYTES = 32 * 1024 * 1024
# Row-tile granularity for tile-grain checksums on large dense blobs
# (the verifiable unit of memory-budgeted partial reads).
_DEFAULT_TILE_CHECKSUM_BYTES = 16 * 1024 * 1024
# Staging window of a pipelined async take: the blocked window stages at
# most this much staging COST before control returns to training, and it
# is the effective in-flight staging budget of the background drain —
# so blocked time and clone RSS are both O(window), not O(state). Two
# max-size chunks (2 x 512 MB, cost 2x while the clone is held) fit, so
# the drain overlaps clone(N+1) with write(N) instead of serializing.
_DEFAULT_ASYNC_STAGE_WINDOW_BYTES = 2 * 1024 * 1024 * 1024
# In-take roofline probes: one probe segment per this many payload
# bytes written, each probe writing (and reading back) this many raw
# bytes through the take's own plugin stack. At the defaults the probe
# overhead is bounded by PROBE_BYTES / PROBE_INTERVAL ≈ 3% of the
# take's I/O, and a 20 GB take self-measures its ceiling ~10 times.
_DEFAULT_PROBE_INTERVAL_BYTES = 2 * 1024 * 1024 * 1024
_DEFAULT_PROBE_BYTES = 64 * 1024 * 1024
_DEFAULT_STAGING_POOL_BYTES = 4 * 1024 * 1024 * 1024


# ------------------------------------------------- tuned-plan overlay
#
# `tpusnap tune` reconcile seam (TPUSNAP_AUTOTUNE=1): an applied plan's
# knob values live HERE, one layer below the environment, and every
# knob lookup consults the env first — so an explicitly-set env var
# always beats the tuner, per lookup, with no copying of tuner values
# into os.environ (which a later explicit `export` could not then
# override, and which child processes would inherit as if the operator
# had set them).
_tuned_lock = threading.Lock()
_tuned_overlay: Dict[str, str] = {}
_tuned_plan_id: Optional[str] = None


def apply_tuned_plan(plan_id: str, knobs: Dict[str, str]) -> Dict[str, str]:
    """Install a tuner plan's knob values as the fallback layer. Knobs
    the environment already sets explicitly are SKIPPED (explicit env
    always wins). Returns the subset actually applied — what the
    take/restore stamps into its history event as ``tuned.knobs``."""
    applied: Dict[str, str] = {}
    with _tuned_lock:
        global _tuned_plan_id
        _tuned_overlay.clear()
        for name, value in knobs.items():
            if name in os.environ:
                continue
            _tuned_overlay[name] = str(value)
            applied[name] = str(value)
        _tuned_plan_id = plan_id if applied else None
    return applied


def clear_tuned_plan() -> None:
    with _tuned_lock:
        global _tuned_plan_id
        _tuned_overlay.clear()
        _tuned_plan_id = None


def tuned_plan() -> Optional[Dict[str, object]]:
    """The currently-applied plan (``{plan_id, knobs}``) or None."""
    with _tuned_lock:
        if _tuned_plan_id is None or not _tuned_overlay:
            return None
        return {"plan_id": _tuned_plan_id, "knobs": dict(_tuned_overlay)}


def _env_get(name: str, default: Optional[str] = None) -> Optional[str]:
    """Knob lookup: explicit environment first, then the applied tuner
    plan, then the default."""
    val = os.environ.get(name)
    if val is not None:
        return val
    with _tuned_lock:
        val = _tuned_overlay.get(name)
    return val if val is not None else default


def _get_float_env(name: str, default: float) -> float:
    val = _env_get(name)
    if val is None:
        return default
    try:
        return float(val)
    except ValueError:
        logger.warning("Ignoring non-numeric %s=%r", name, val)
        return default


def _get_int_env(name: str, default: int) -> int:
    val = _env_get(name)
    if val is None:
        return default
    try:
        return int(val)
    except ValueError:
        logger.warning("Ignoring non-integer %s=%r", name, val)
        return default


def get_max_chunk_size_bytes() -> int:
    return _get_int_env(_MAX_CHUNK_SIZE_ENV_VAR, _DEFAULT_MAX_CHUNK_SIZE_BYTES)


def get_max_shard_size_bytes() -> int:
    return _get_int_env(_MAX_SHARD_SIZE_ENV_VAR, _DEFAULT_MAX_SHARD_SIZE_BYTES)


def get_slab_size_threshold_bytes() -> int:
    """A slab's capacity (``TPUSNAP_SLAB_SIZE_THRESHOLD_BYTES``, 128 MiB):
    the batcher closes a slab before its members' bytes pass it. It is
    not the largest member: a dense leaf is a slab member only while its
    staging cost is under the smaller of this and the batcher's fixed
    member size (16 MiB, ``batcher._MAX_SLAB_MEMBER_BYTES``). A slab pays
    when it replaces many small objects and transfers; a leaf of 16 MiB
    or more already fills its own DMA and its own blob, and inside a slab
    it would cross the bus a second time and be hashed on the one staging
    thread. Set below 16 MiB, the threshold bounds members too."""
    return _get_int_env(
        _SLAB_SIZE_THRESHOLD_ENV_VAR, _DEFAULT_SLAB_SIZE_THRESHOLD_BYTES
    )


def is_batching_disabled() -> bool:
    return os.environ.get(_DISABLE_BATCHING_ENV_VAR, "0") == "1"


def is_device_batching_disabled() -> bool:
    return os.environ.get(_DISABLE_DEVICE_BATCHING_ENV_VAR, "0") == "1"


def is_partitioner_disabled() -> bool:
    return os.environ.get(_DISABLE_PARTITIONER_ENV_VAR, "0") == "1"


def is_native_disabled() -> bool:
    return os.environ.get(_DISABLE_NATIVE_ENV_VAR, "0") == "1"


def is_direct_io_disabled() -> bool:
    """O_DIRECT file writes (fs plugin): on by default; the native layer
    falls back to buffered writes automatically on filesystems without
    O_DIRECT support, so this knob exists for debugging."""
    return os.environ.get(_DISABLE_DIRECT_IO_ENV_VAR, "0") == "1"


def is_checksum_disabled() -> bool:
    """Per-blob CRC32C integrity checksums: recorded at stage time and
    verified on read, both on by default. Disable only when reading
    snapshots from untrusted-layout sources."""
    return os.environ.get(_DISABLE_CHECKSUM_ENV_VAR, "0") == "1"


def is_dontcache_disabled() -> bool:
    """Uncached buffered writes (RWF_DONTCACHE, Linux 6.14+) for
    unaligned sources: on by default; the native layer falls back to the
    O_DIRECT bounce pipeline automatically where unsupported."""
    return os.environ.get(_DISABLE_DONTCACHE_ENV_VAR, "0") == "1"


def get_direct_io_qd() -> int:
    """In-flight chunk writes per file on the O_DIRECT path."""
    return _get_int_env(_DIRECT_IO_QD_ENV_VAR, _DEFAULT_DIRECT_IO_QD)


def get_direct_io_chunk_bytes() -> int:
    return _get_int_env(
        _DIRECT_IO_CHUNK_ENV_VAR, _DEFAULT_DIRECT_IO_CHUNK_BYTES
    )


def get_tile_checksum_bytes() -> int:
    return _get_int_env(_TILE_CHECKSUM_ENV_VAR, _DEFAULT_TILE_CHECKSUM_BYTES)


def get_scrub_concurrency() -> int:
    """Blob ranges the integrity scrub keeps in flight (peak memory is
    this many scratch buffers). Raise for high-latency storage (cloud
    scrubs), lower for tight-memory hosts."""
    return max(1, _get_int_env(_SCRUB_CONCURRENCY_ENV_VAR, 4))


def is_durable_commit_enabled() -> bool:
    """Make a returned take survive power loss: every blob file is
    fsync'd after its write, and the metadata commit fsyncs its temp
    file, renames, then fsyncs every directory the snapshot created —
    data, dirents and the commit record all on stable storage, in that
    order. Off by default: the fsyncs after a multi-GB take force the
    device to flush everything just written (~2 s measured on the dev
    host's virtio disk), a cost the baselines tpusnap is benchmarked
    against (torch.save, the reference) never pay. Without it the
    commit is still crash-SAFE (temp+rename: never torn, at worst
    invisible/incomplete-and-invisible); metadata REWRITES of committed
    snapshots (materialize, retention) fsync their own commit
    unconditionally — there the flush is cheap and the downside is
    destroying good state."""
    return os.environ.get(_DURABLE_COMMIT_ENV_VAR, "0") == "1"


def is_dedup_hash_recording_forced() -> bool:
    """Record 64-bit per-tile dedup hashes on EVERY take, not just
    incremental ones — set on the FULL base take of a planned
    incremental chain so the first increment can already make
    tile-grain skip decisions against it (otherwise the chain reaches
    tile grain from the second increment on). Costs one extra fused
    hash lane (~2x the hash pass) on large tiled blobs."""
    return os.environ.get(_RECORD_DEDUP_HASHES_ENV_VAR, "0") == "1"


def is_journal_disabled() -> bool:
    """Crash-safe take journal (:mod:`tpusnap.lifecycle`): on by default
    — rank 0 marks the take before any blob write (so fsck can classify
    a SIGKILLed take) and every rank records per-blob completion hashes
    (the salvage-resume evidence; one fused CRC32C+XXH64 pass per
    non-slab blob on the write path, overlapped with storage I/O on a
    worker thread). ``TPUSNAP_DISABLE_JOURNAL=1`` turns the whole layer
    off: crashed takes then classify as foreign and retakes restart
    from byte zero."""
    return os.environ.get(_DISABLE_JOURNAL_ENV_VAR, "0") == "1"


def is_telemetry_enabled() -> bool:
    """Per-take SPAN capture + persisted Chrome traces
    (:mod:`tpusnap.telemetry`): on by default — the disabled path of a
    span is a dict lookup, and the tier-1 overhead guard bounds the
    enabled cost at <10% on a small take. ``TPUSNAP_TELEMETRY=0``
    disables span capture and trace persistence; COUNTERS (retries,
    faults, pool hits, bytes written) stay on either way."""
    return os.environ.get(_TELEMETRY_ENV_VAR, "1") != "0"


def get_stall_deadline_s() -> float:
    """No-forward-progress window after which a take's stall watchdog
    (:mod:`tpusnap.progress`) emits its structured WARNING naming the
    blocked op and — when attribution is available — the ranks that have
    not arrived at the barrier it is stuck in. Well under the 600 s
    barrier timeout by design: the point is an actionable log in
    seconds, not another timeout."""
    return max(0.1, _get_float_env(_STALL_DEADLINE_ENV_VAR, 30.0))


def get_heartbeat_interval_s() -> float:
    """Cadence of the per-rank heartbeat pump: progress records are
    published at most once per interval (and only when something
    changed, with a periodic keep-alive) — O(world) KV keys per
    interval, never per op."""
    return max(0.02, _get_float_env(_HEARTBEAT_INTERVAL_ENV_VAR, 0.5))


def get_telemetry_dir() -> str:
    """Local directory for telemetry that cannot live inside the
    snapshot — restore traces (the snapshot is immutable once
    committed). Defaults to a stable per-user tmp path (uid-suffixed:
    a shared-host /tmp dir owned by the first user would EACCES every
    other user's trace writes); override with
    ``TPUSNAP_TELEMETRY_DIR``."""
    import tempfile

    uid = os.getuid() if hasattr(os, "getuid") else "u"
    return os.environ.get(_TELEMETRY_DIR_ENV_VAR) or os.path.join(
        tempfile.gettempdir(), f"tpusnap-telemetry-{uid}"
    )


_KNOWN_METRICS_FORMATS = ("prom", "jsonl")
# Unknown-format tokens already warned about: get_metrics_export runs at
# every take/restore begin, and one typo must not spam a WARNING per
# checkpoint for the job's whole life.
_warned_metrics_formats: set = set()


def get_metrics_export() -> tuple:
    """Fleet metrics export formats (:mod:`tpusnap.metrics_export`),
    comma-separated: ``prom`` (Prometheus textfile, atomic ``.prom``
    rewrite per take/restore summary for node-exporter textfile
    collection) and/or ``jsonl`` (structured per-summary event lines,
    rotation-bounded). Empty (the default) exports nothing; unknown
    names warn once per process and are skipped rather than failing a
    take."""
    raw = os.environ.get(_METRICS_EXPORT_ENV_VAR, "")
    out = []
    for tok in raw.replace(";", ",").split(","):
        tok = tok.strip().lower()
        if not tok:
            continue
        if tok not in _KNOWN_METRICS_FORMATS:
            if tok not in _warned_metrics_formats:
                _warned_metrics_formats.add(tok)
                logger.warning(
                    "Ignoring unknown %s format %r (known: %s)",
                    _METRICS_EXPORT_ENV_VAR,
                    tok,
                    ", ".join(_KNOWN_METRICS_FORMATS),
                )
            continue
        if tok not in out:
            out.append(tok)
    return tuple(out)


def get_metrics_dir() -> str:
    """Directory the export sinks write into (``.prom`` textfiles, the
    JSONL event log). Defaults to the telemetry dir so a node's whole
    observability surface lives under one path; point
    ``TPUSNAP_METRICS_DIR`` at the node-exporter textfile collector's
    directory in production."""
    return os.environ.get(_METRICS_DIR_ENV_VAR) or get_telemetry_dir()


def is_history_enabled() -> bool:
    """Cross-run history recording (:mod:`tpusnap.history`): every
    COMPLETED take/restore appends one summary line to the per-host
    ``TPUSNAP_TELEMETRY_DIR/history.jsonl`` (size-bounded, crash-
    tolerant), queryable by ``python -m tpusnap history`` and its
    ``--check`` regression gate. ``TPUSNAP_HISTORY=0`` disables the
    append (the file is never written)."""
    return os.environ.get(_HISTORY_ENV_VAR, "1") != "0"


def get_history_max_bytes() -> int:
    """Size bound on history.jsonl: when an append pushes the file past
    this, the oldest lines are compacted away (newest kept, atomic
    rewrite). Floor of 64 KiB so a misconfigured bound cannot thrash
    every append."""
    return max(
        64 * 1024, _get_int_env(_HISTORY_MAX_BYTES_ENV_VAR, 4 * 1024 * 1024)
    )


def is_access_ledger_enabled() -> bool:
    """Read-side access attribution (:mod:`tpusnap.access`): every
    restore / ``read_object`` records which manifest leaves and byte
    ranges it actually read, aggregated in memory and appended as one
    JSONL summary line per read scope to the per-reader ledger sidecar
    (``TPUSNAP_TELEMETRY_DIR/access/<digest>/<job_id>.jsonl``) that
    ``tpusnap heatmap`` and the fleet fold merge across readers. On by
    default — the per-read cost is one dict update on an already-
    telemetry-instrumented path, bounded by the tier-1 ≤10% overhead
    guard. ``TPUSNAP_ACCESS_LEDGER=0`` disables recording (no ledger
    file is ever written); also off whenever telemetry as a whole is
    disabled."""
    return (
        os.environ.get(_ACCESS_LEDGER_ENV_VAR, "1") != "0"
        and is_telemetry_enabled()
    )


def get_access_ledger_max_bytes() -> int:
    """Size bound on one reader's access ledger file: when an append
    pushes it past this, the file rotates to ``<name>.1`` (previous
    rotation overwritten) — same single-generation scheme as the JSONL
    metrics sink. Floor of 64 KiB so a misconfigured bound cannot
    rotate on every flush."""
    return max(
        64 * 1024,
        _get_int_env(_ACCESS_LEDGER_MAX_BYTES_ENV_VAR, 8 * 1024 * 1024),
    )


def get_stage_threads() -> int:
    """Worker threads of the write scheduler's staging executor (the
    clone / DtoH / serialize pass). Default 1. The historical anomaly
    (~1 GB/s aggregate for 4 threads vs ~4 GB/s for one on the dev
    host) was NESTED parallelism: each executor thread already runs a
    4-way-internal native copy pass, so 4 executor threads
    oversubscribed the memory system 16 ways — see
    :func:`get_native_copy_threads`, which now divides the internal
    fan-out by this knob so the total copy-thread budget stays
    constant. Raising this is therefore safe everywhere and shifts
    parallelism grain (useful when per-request Python overhead, not
    bandwidth, is the bound); clamped to [1, 16]."""
    return max(1, min(16, _get_int_env(_STAGE_THREADS_ENV_VAR, 1)))


def get_async_stage_window_bytes() -> Optional[int]:
    """Staging window of a pipelined async take (see
    :mod:`tpusnap.scheduler`): ``async_take`` returns control once the
    first window of the write requests that count towards it is
    staged — those whose bytes the caller could write in place (numpy,
    ``pinned_host`` and CPU-backend arrays, objects, any stager that
    does not answer otherwise); an accelerator-resident leaf is held
    by reference and never counts. The background drain stages the
    rest interleaved with storage I/O under this in-flight bound —
    blocked time and clone RSS are O(window) instead of O(state). The
    bound charges the buffers that are tpusnap's own (clones, slabs,
    turned or compressed blobs, the host value of an owned copy); an
    accelerator leaf that crosses as it lies is staged as the host
    value kept on the caller's own array, of which a write's end frees
    nothing, and is not charged.
    ``0`` disables pipelining: ``async_take`` then stages the WHOLE
    state, device leaves too, before returning (the pre-pipeline strict
    semantics, for callers that mutate host-aliasing state in place or
    donate device state immediately after control returns instead of
    using ``PendingSnapshot.wait_staged()``)."""
    val = _get_int_env(
        _ASYNC_STAGE_WINDOW_ENV_VAR, _DEFAULT_ASYNC_STAGE_WINDOW_BYTES
    )
    return val if val > 0 else None


def is_async_cow_enabled() -> bool:
    """Copy-on-write async staging for host-aliasing arrays (numpy /
    pinned_host / CPU-backend device arrays), ON BY DEFAULT since
    round 14 (ROADMAP 5: the 20 GB take spent 13.5 of 14.5 s in the
    clone pass — frozen layers should clone nothing): instead of the
    defensive clone, the blocked window records the fused
    CRC32C(+XXH64) hash of the live bytes and the write path re-hashes
    after the storage write — a mismatch (the caller mutated the array
    mid-take) fails the take loudly instead of committing torn data.
    ``PendingSnapshot.staged()/wait_staged()`` are COW-aware: for a
    take in which a stager went copy-on-write they report THIS RANK's
    write drain, so ``staged() ⟹ safe to mutate`` holds exactly as
    before. They read that off the take, not off this knob: a state of
    accelerator leaves has no such stager whatever the knob says, and
    its rendezvous is staging-complete. ``TPUSNAP_ASYNC_COW=0`` is the escape
    hatch back to defensive cloning, which strengthens the guarantee
    from "mutation is detected and fails the take" to "mutation cannot
    corrupt" at the cost of a full clone pass per take."""
    return os.environ.get(_ASYNC_COW_ENV_VAR, "1") != "0"


def is_probe_enabled() -> bool:
    """In-take roofline probes (``TPUSNAP_PROBE=1``, off by default):
    the write scheduler interleaves tiny raw write/read probe segments
    between I/O windows — through the SAME storage plugin stack the
    take's blobs use — so every take self-measures its achievable
    storage ceiling and carries a drift-immune ``roofline_fraction`` in
    its summary, rollup and history event. Opt-in because the probes
    cost real I/O (bounded by PROBE_BYTES/PROBE_INTERVAL, ~3% at the
    defaults) and only run when telemetry is enabled. The restore
    scheduler runs the same probes between its read windows, feeding
    ``restore_roofline_fraction`` from the read leg."""
    return _env_get(_PROBE_ENV_VAR, "0") == "1"


def is_autotune_enabled() -> bool:
    """``TPUSNAP_AUTOTUNE=1`` (off by default): at take/restore begin,
    compute the `tpusnap tune` plan for this backend/kind/world-size
    cell from the local history and apply it through the tuned-plan
    overlay. Explicit env vars always win over the plan; the knobs a
    run actually applied are stamped into its history event as
    ``tuned: {plan_id, knobs}`` so `history --check` can attribute (and
    gate) any regression the tuner causes."""
    return _env_get(_AUTOTUNE_ENV_VAR, "0") == "1"


def get_probe_interval_bytes() -> int:
    """Payload bytes written between in-take roofline probe segments.
    Floor of 16 MiB so a misconfigured cadence cannot turn the take
    into a probe benchmark."""
    return max(
        16 * 1024 * 1024,
        _get_int_env(_PROBE_INTERVAL_ENV_VAR, _DEFAULT_PROBE_INTERVAL_BYTES),
    )


def get_probe_bytes() -> int:
    """Raw bytes one probe segment writes (then reads back) through the
    take's plugin stack, split across a few concurrent streams to
    measure the AGGREGATE ceiling the take's own parallel writes see.
    Floor of 1 MiB: smaller probes measure syscall latency, not
    bandwidth."""
    return max(
        1024 * 1024, _get_int_env(_PROBE_BYTES_ENV_VAR, _DEFAULT_PROBE_BYTES)
    )


def get_staging_pool_bytes() -> int:
    """Cap on the reusable aligned staging-buffer pool
    (:mod:`tpusnap._staging_pool`): released async-clone buffers up to
    this many bytes are parked and handed back warm (no first-touch
    page faults) to later takes and later pipelined-staging windows.
    ``0`` disables the pool entirely (every clone allocates fresh)."""
    return max(0, _get_int_env(_STAGING_POOL_ENV_VAR, _DEFAULT_STAGING_POOL_BYTES))


def is_flight_enabled() -> bool:
    """Black-box flight recorder (:mod:`tpusnap.flight`): on by default
    — a bounded, lock-light ring buffer of structured events (spans,
    phases, journal writes, retries, faults, barriers, stalls, probes)
    flushed to crash-surviving sidecars at the heartbeat cadence, so a
    SIGKILLed/wedged take leaves a forensic timeline
    (``python -m tpusnap timeline``) instead of just a journal marker.
    ``TPUSNAP_FLIGHT=0`` disables recording AND flushing entirely (the
    disabled record path is one attribute check)."""
    return os.environ.get(_FLIGHT_ENV_VAR, "1") != "0"


def get_flight_ring_size() -> int:
    """Flight-recorder ring capacity in EVENTS: the black box keeps the
    newest this-many events (older ones are evicted and counted as
    dropped in the flushed header). Bounded by design — the recorder's
    memory and flush cost are O(ring), never O(take). Floor of 256 so a
    misconfigured ring cannot reduce the black box to noise."""
    return max(256, _get_int_env(_FLIGHT_RING_ENV_VAR, 4096))


def get_flight_flush_interval_s() -> float:
    """Cadence of the flight recorder's crash-surviving flush
    (piggybacked on the heartbeat pump): the sidecar is rewritten
    atomically at most once per interval, so after a SIGKILL — which no
    handler can catch — AT MOST this many seconds of events are lost.
    This knob IS the documented loss bound. Defaults to the heartbeat
    interval (``TPUSNAP_HEARTBEAT_INTERVAL_S``)."""
    val = _get_float_env(_FLIGHT_FLUSH_ENV_VAR, -1.0)
    if val <= 0:
        return get_heartbeat_interval_s()
    return max(0.02, val)


def get_slo_rpo_threshold_s() -> float:
    """Recovery-point objective threshold (:mod:`tpusnap.slo`): when
    the seconds since the last committed take exceed this, the tracker
    emits one edge-triggered ``slo_breach`` flight event + counter per
    episode, the breach flag rides the exported gauges/sidecar, and
    ``python -m tpusnap slo --check`` exits 2. ``0`` (the default)
    means no RPO objective is set — the gauges still publish."""
    return max(0.0, _get_float_env(_SLO_RPO_ENV_VAR, 0.0))


def get_slo_rto_threshold_s() -> float:
    """Recovery-time objective threshold (:mod:`tpusnap.slo`): breach
    when the history-derived estimated restore time of the last
    committed snapshot exceeds this many seconds. ``0`` (the default)
    = unset. The estimate needs ≥3 comparable restore events in
    ``history.jsonl``; with a threshold set and no estimate available,
    ``slo --check`` exits 3 (no verdict), never a silent pass."""
    return max(0.0, _get_float_env(_SLO_RTO_ENV_VAR, 0.0))


def get_slo_stream_cadence_x() -> float:
    """Stream-cadence gate multiplier of ``slo --check``
    (:mod:`tpusnap.slo`): while a delta stream is LIVE (its SLO record
    advertises a ``stream_cadence_s`` and is not a final record), the
    observed time since the last commit must stay under this many
    multiples of the declared cadence — beyond it the verdict is a
    breach (exit 2): the stream has silently stalled and exposure is
    growing past what the operator declared. ``0`` disables the gate;
    values are floored at 1 (below 1x a healthy stream could never
    pass). Default 3x."""
    val = _get_float_env(_SLO_STREAM_CADENCE_X_ENV_VAR, 3.0)
    if val <= 0:
        return 0.0
    return max(1.0, val)


def get_delta_cadence_s() -> float:
    """Default micro-commit cadence of a delta stream
    (:meth:`tpusnap.Snapshot.stream` / :class:`tpusnap.delta.DeltaStream`)
    when the call doesn't pass ``cadence_s``: the stream commits one
    journaled incremental micro-snapshot per interval, so this bounds
    the stream's recovery-point objective — a crash replays base +
    committed delta chain and loses at most ~one interval of work.
    Floor 0.1 s (a micro-commit is a real two-phase-committed take;
    sub-100ms cadences would spend the whole interval committing)."""
    return max(0.1, _get_float_env(_DELTA_CADENCE_ENV_VAR, 5.0))


def get_delta_max_chain() -> int:
    """Chain-compaction threshold of a delta stream: once the chain
    from the base to the head exceeds this many members, the stream
    materializes the head (the existing ``materialize`` path — copying
    referenced blobs in, checksum-verified, committed atomically) so it
    becomes the new self-contained base, and retires the superseded
    members. Bounds both restore fan-in (how many sibling directories a
    head's blob references span) and the storage a long-running stream
    pins. Clamped to [2, 1024]."""
    return max(2, min(1024, _get_int_env(_DELTA_MAX_CHAIN_ENV_VAR, 8)))


def is_tier_drain_enabled() -> bool:
    """Background cloud drain of the write-back tier
    (:mod:`tpusnap.tiering`): on by default — a take to a
    ``tier+local=...+remote=...`` URL commits to the local tier at disk
    speed and the uploader thread drains blobs to the remote tier in the
    background, converging to ``remote-durable``. ``0`` disables the
    automatic drain: takes stay ``local-committed`` until
    ``python -m tpusnap drain`` is run (useful for tests and for
    operators who schedule drains out of band)."""
    return os.environ.get(_TIER_DRAIN_ENV_VAR, "1") != "0"


def get_tier_op_deadline_s() -> float:
    """Per-op retry deadline (``retry_deadline_sec``) of the write-back
    uploader's REMOTE plugin: short by design — once a single upload has
    made no progress for this long, the retry middleware gives up
    (``retry.exhausted``) and the uploader's own sustained-outage mode
    (circuit breaker + capped exponential backoff, takes keep committing
    locally) takes over. The default 600 s payload deadline would park
    the drain inside one op for 10 minutes before the outage machinery
    ever saw a failure."""
    return max(0.05, _get_float_env(_TIER_OP_DEADLINE_ENV_VAR, 60.0))


def get_tier_outage_threshold() -> int:
    """Consecutive failed remote uploads before the uploader's circuit
    opens: the drain enters DEGRADED mode (edge-triggered
    ``tier_degraded`` flight event, `tpusnap_tier_degraded` gauge,
    capped-backoff probing) instead of hammering a down endpoint."""
    return max(1, _get_int_env(_TIER_OUTAGE_THRESHOLD_ENV_VAR, 3))


def get_tier_backoff_cap_s() -> float:
    """Cap on the uploader's degraded-mode exponential backoff between
    remote probes during a sustained outage."""
    return max(0.05, _get_float_env(_TIER_BACKOFF_CAP_ENV_VAR, 30.0))


def get_tier_local_retention_s() -> float:
    """Hot-local-cache retention policy for ``gc --evict-local``: local
    payload blobs of a ``remote-durable`` snapshot may be reclaimed only
    once the remote-durable marker is at least this old. ``0`` (the
    default) lets an explicit eviction reclaim immediately; a fleet that
    wants the last N minutes of checkpoints restorable at local-disk
    speed sets this to that window."""
    return max(0.0, _get_float_env(_TIER_LOCAL_RETENTION_ENV_VAR, 0.0))


_KNOWN_COMPRESS_MODES = ("auto", "on", "off", "lz4")
_warned_compress_modes: set = set()


def get_compress_mode() -> str:
    """Per-take fused tile compression (:mod:`tpusnap.compress`):

    - ``auto`` (default) — a per-take decision MEASURED ON THE TAKE: the
      codec runs over an 8 MiB sample of the state's own host bytes, and
      the take compresses when rate x (1 - ratio) of that sample, the
      bytes the codec takes off the pipe per second, clearly outruns the
      pipe's probe-reported ceiling (compressible state to cloud,
      virtio, the write-back tier's remote drain); it bypasses when the
      state barely compresses or local disk outruns the codec, and
      whenever a number is missing. Takes whose eligible payload is
      below the auto floor always bypass (small takes are not worth
      the codec bookkeeping, a sample or a probe).
    - ``on`` — compress every eligible blob regardless of the pipe;
      takes no sample.
    - ``off`` — bypass entirely.
    - ``lz4`` — force the named codec family (same as ``on`` today;
      the name exists so a future codec can be pinned explicitly).

    Unknown values warn once per process and fall back to ``auto``."""
    raw = (_env_get(_COMPRESS_ENV_VAR) or "auto").strip().lower()
    if raw not in _KNOWN_COMPRESS_MODES:
        if raw not in _warned_compress_modes:
            _warned_compress_modes.add(raw)
            logger.warning(
                "Ignoring unknown %s=%r (known: %s); using auto",
                _COMPRESS_ENV_VAR,
                raw,
                ", ".join(_KNOWN_COMPRESS_MODES),
            )
        return "auto"
    return raw


def get_compress_min_blob_bytes() -> int:
    """Per-blob eligibility floor for fused tile compression: blobs
    smaller than this bypass the codec (slab members and tiny arrays
    cost more in bookkeeping than the pipe saves). Floor of 64 KiB."""
    return max(
        64 * 1024, _get_int_env(_COMPRESS_MIN_BLOB_ENV_VAR, 1024 * 1024)
    )


def get_barrier_timeout_s() -> float:
    """Hard deadline of every blocking collective/KV wait (the
    coordination-service barriers in :mod:`tpusnap.comm`, the
    ``LinearBarrier``/``KVStore.get`` polls in :mod:`tpusnap.dist_store`).
    Historically three separate literals (600 s in comm/dist_store,
    1800 s on the async commit barrier — see
    :func:`get_commit_barrier_timeout_s`); one knob now routes them all.
    This is the LAST-RESORT bound: with liveness leases on
    (``TPUSNAP_LIVENESS_TTL_S``) a dead peer fails the wait within
    ~2x the lease TTL, so the full timeout is only burned when the
    coordination service itself is unreachable. Floor of 1 s."""
    return max(1.0, _get_float_env(_BARRIER_TIMEOUT_ENV_VAR, 600.0))


def get_commit_barrier_timeout_s() -> float:
    """Deadline of the async commit's LinearBarrier waits — 3x the
    collective timeout, preserving the historical 600 s/1800 s ratio
    (the commit barrier waits on every rank's full residual I/O drain,
    not just a collective round-trip)."""
    return 3.0 * get_barrier_timeout_s()


def get_liveness_ttl_s() -> float:
    """Rank-liveness lease TTL (:mod:`tpusnap.liveness`): each rank's
    lease record (published over the coordination KV by the heartbeat
    pump — no extra thread) must advance within this window or peers
    blocked in a collective/commit wait declare the rank dead and raise
    :class:`~tpusnap.liveness.RankFailedError` naming it, within ~2x
    this TTL instead of parking until the barrier timeout. ``0``
    disables the liveness layer (waits fall back to the bare
    ``TPUSNAP_BARRIER_TIMEOUT_S``). Requires telemetry (the lease rides
    the heartbeat pump); keep the value well above the heartbeat
    interval — the floor is 4x ``TPUSNAP_HEARTBEAT_INTERVAL_S``."""
    ttl = _get_float_env(_LIVENESS_TTL_ENV_VAR, 15.0)
    if ttl <= 0:
        return 0.0
    return max(4.0 * get_heartbeat_interval_s(), ttl)


_KNOWN_RANK_FAILURE_POLICIES = ("abort", "degrade")
_warned_rank_failure_policies: set = set()


def get_rank_failure_policy() -> str:
    """What a multi-process take does when liveness declares a peer
    dead mid-take:

    - ``abort`` (default) — the detecting rank raises
      :class:`~tpusnap.liveness.RankFailedError`, publishes it through
      the take-abort monitor so every survivor aborts within seconds,
      and the path is left torn (fsck/`timeline` name the dead rank; a
      retake salvages the survivors' completed blobs via the dual-hash
      evidence rule).
    - ``degrade`` — a take whose dead rank held only REPLICATED
      partitions is completed by the survivors: the dead rank's
      replicated write assignments are adopted by live ranks
      (re-planned deterministically), the commit barrier shrinks to the
      live set, and ``metadata.extras["degraded"]`` records the
      adoption. A dead rank holding sharded/unique partitions (or an
      incremental take) still aborts — its bytes are unrecoverable.

    Must be set identically on every rank. Unknown values warn once per
    process and fall back to ``abort``."""
    raw = os.environ.get(_RANK_FAILURE_ENV_VAR, "abort").strip().lower()
    if raw not in _KNOWN_RANK_FAILURE_POLICIES:
        if raw not in _warned_rank_failure_policies:
            _warned_rank_failure_policies.add(raw)
            logger.warning(
                "Ignoring unknown %s=%r (known: %s); using abort",
                _RANK_FAILURE_ENV_VAR,
                raw,
                ", ".join(_KNOWN_RANK_FAILURE_POLICIES),
            )
        return "abort"
    return raw


def get_native_copy_threads() -> int:
    """Internal threads of ONE native copy/hash pass (``_native.memcpy``
    and the fused clone+CRC(+XXH64) tile passes), derived so the TOTAL
    copy-thread budget stays ~constant: ``stage_threads × this ≈ 4``.
    The ROADMAP 5 staging anomaly (``TPUSNAP_STAGE_THREADS=4`` measured
    ~1 GB/s aggregate vs ~4 GB/s for 1 on the dev host) was NESTED
    parallelism, not NUMA: each staging executor thread already fans
    out to 4 native memcpy threads, so 4 executor threads ran 16 copy
    threads on a memory system that saturates around 4 — past
    saturation, extra copy threads are pure cache-line ping-pong and
    context switching. Measured on a 24-core host: equal-total-budget
    splits are equivalent (1×4 ≈ 2×2 ≈ 4×1 ≈ 28 GB/s), confirming the
    total is what matters. With this divisor, raising
    ``TPUSNAP_STAGE_THREADS`` only shifts the parallelism grain (and
    overlaps per-request Python overhead) — it can no longer
    oversubscribe the memory system, which is why the auto-default of
    1 executor thread stays safe everywhere."""
    return max(1, 4 // get_stage_threads())


def is_lockcheck_enabled() -> bool:
    """Runtime lock-order watchdog (:mod:`tpusnap.devtools.lockwatch`),
    OPT-IN via ``TPUSNAP_LOCKCHECK=1``: every ``threading.Lock``/
    ``RLock`` created after import is wrapped to record the per-thread
    held-lock stack and a global lock-order graph; AB/BA cycles
    (potential deadlocks) and locks held across storage I/O are
    reported at process exit and via the lockwatch API. Off by default:
    the instrumentation adds a pure-Python hop to every lock
    acquisition. The tier-1 test run enables it so the whole suite
    doubles as a deadlock detector."""
    return os.environ.get(_LOCKCHECK_ENV_VAR, "0") == "1"


def get_memory_budget_override_bytes() -> Optional[int]:
    if _env_get(_MEMORY_BUDGET_ENV_VAR) is None:
        return None
    val = _get_int_env(_MEMORY_BUDGET_ENV_VAR, -1)
    return val if val > 0 else None


_NODE_NAME_ENV_VAR = "TPUSNAP_NODE_NAME"


def get_node_name() -> str:
    """The identity used to decide which ranks SHARE A HOST (the
    per-host memory-budget divisor gathers these). Defaults to the OS
    hostname; ``TPUSNAP_NODE_NAME`` overrides it for containerized
    jobs where every pod reports a unique hostname despite sharing a
    node (kubernetes), and for multi-host simulation in tests."""
    import socket

    return os.environ.get(_NODE_NAME_ENV_VAR) or socket.gethostname()


def get_job_id() -> str:
    """The identity of THIS training job on every observability
    artifact — telemetry summaries, history events, heartbeat records,
    flight headers, SLO sidecars, Prometheus filenames/labels, and the
    fleet status records under ``TPUSNAP_FLEET_DIR``. Defaults to
    ``<node>-<pid>`` so two jobs sharing a telemetry/metrics/fleet
    directory never collide even when nobody set the knob; a
    MULTI-PROCESS job must set ``TPUSNAP_JOB_ID`` identically on every
    rank (the host-pid default would split one job into per-rank
    identities). Sanitized to filename/label-safe characters: the id
    lands in file names and Prometheus label values."""
    explicit = get_explicit_job_id()
    if explicit is not None:
        return explicit
    raw = f"{get_node_name()}-{os.getpid()}"
    clean = "".join(c if (c.isalnum() or c in "._-") else "-" for c in raw)
    return clean or "job"


def get_explicit_job_id() -> Optional[str]:
    """``TPUSNAP_JOB_ID`` exactly as configured (sanitized), or None
    when unset — the comparability key history's regression baseline
    filters on. :func:`get_job_id`'s host-pid DEFAULT is deliberately
    absent here: it changes every process, and stamping it into history
    events would make every cross-run baseline structurally empty
    (one-take-per-process fleets would never accumulate a gradeable
    window)."""
    raw = os.environ.get(_JOB_ID_ENV_VAR)
    if not raw:
        return None
    clean = "".join(c if (c.isalnum() or c in "._-") else "-" for c in raw)
    return clean or None


def get_fleet_dir() -> Optional[str]:
    """Shared cross-job status directory (``TPUSNAP_FLEET_DIR``): when
    set, rank 0 of every instrumented job mirrors its heartbeat/SLO/
    tier state into ``<dir>/<job_id>.json`` (atomic rewrite, riding the
    heartbeat pump — :mod:`tpusnap.fleet`), and ``python -m tpusnap
    fleet`` folds all jobs' records into fleet rollups. Unset/empty =
    the fleet layer is off (zero per-take cost)."""
    val = os.environ.get(_FLEET_DIR_ENV_VAR)
    return val or None


def get_cas_dir() -> Optional[str]:
    """Shared content-addressed blob store (``TPUSNAP_CAS_DIR``,
    :mod:`tpusnap.cas`): a directory — or a storage URL, e.g.
    ``chaos+fs:///store`` so chaos plans can target store I/O — that
    every CAS-composed take publishes payload blobs into, keyed by
    their (CRC32C, XXH64) dual hash. When set, a plain ``fs`` take URL
    is auto-composed with the CAS layer (equivalent to the explicit
    ``cas+fs://`` scheme); snapshots then hold ref records instead of
    private payload copies. Unset/empty = the layer is off."""
    val = os.environ.get(_CAS_DIR_ENV_VAR)
    return val or None


def get_cas_grace_s() -> float:
    """Grace window of the store's mark-and-sweep gc
    (:func:`tpusnap.cas.gc_store`): an UNMARKED blob, a stale publish
    intent, a ``.tmp.*`` torn-publish leftover or a stale root record
    is swept only once it is at least this old — young debris may be a
    concurrent publisher mid-adoption whose ref record simply hasn't
    landed yet. Lowering it below the duration of a take invites the
    publish-vs-gc race the intent records exist to close."""
    return max(0.0, _get_float_env(_CAS_GRACE_ENV_VAR, 900.0))


def get_cas_lease_ttl_s() -> float:
    """TTL of the per-store gc lock lease (``gc.lock``): a second
    ``gc --store`` against the same store is refused while a live lease
    exists, and a lease abandoned by a SIGKILLed sweeper is stealable
    once this old (the PR 15 lease shape applied to stores)."""
    return max(0.5, _get_float_env(_CAS_LEASE_TTL_ENV_VAR, 60.0))


def get_cas_remote() -> Optional[str]:
    """Remote mirror URL of the content-addressed store: when set (or
    recorded in the store's ``config.json``), the tiering drain uploads
    each unique store blob ONCE store-wide to ``<remote>/blobs/<key>``
    — recording dual-hash evidence in the store-level upload journal —
    and store reads fall back to the mirror for locally-evicted blobs.
    Unset = the store is local-only (``gc --evict-local`` then refuses
    to evict CAS-referenced payloads)."""
    val = os.environ.get(_CAS_REMOTE_ENV_VAR)
    return val or None


@contextlib.contextmanager
def _override_env(name: str, value: Optional[str]) -> Generator[None, None, None]:
    prev = os.environ.get(name)
    try:
        if value is None:
            os.environ.pop(name, None)
        else:
            os.environ[name] = value
        yield
    finally:
        if prev is None:
            os.environ.pop(name, None)
        else:
            os.environ[name] = prev


@contextlib.contextmanager
def override_max_chunk_size_bytes(nbytes: int) -> Generator[None, None, None]:
    with _override_env(_MAX_CHUNK_SIZE_ENV_VAR, str(nbytes)):
        yield


@contextlib.contextmanager
def override_max_shard_size_bytes(nbytes: int) -> Generator[None, None, None]:
    with _override_env(_MAX_SHARD_SIZE_ENV_VAR, str(nbytes)):
        yield


@contextlib.contextmanager
def override_slab_size_threshold_bytes(nbytes: int) -> Generator[None, None, None]:
    with _override_env(_SLAB_SIZE_THRESHOLD_ENV_VAR, str(nbytes)):
        yield


@contextlib.contextmanager
def override_batching_disabled(disabled: bool) -> Generator[None, None, None]:
    with _override_env(_DISABLE_BATCHING_ENV_VAR, "1" if disabled else "0"):
        yield


@contextlib.contextmanager
def override_device_batching_disabled(disabled: bool) -> Generator[None, None, None]:
    with _override_env(_DISABLE_DEVICE_BATCHING_ENV_VAR, "1" if disabled else "0"):
        yield


@contextlib.contextmanager
def override_memory_budget_bytes(nbytes: int) -> Generator[None, None, None]:
    with _override_env(_MEMORY_BUDGET_ENV_VAR, str(nbytes)):
        yield


@contextlib.contextmanager
def override_direct_io_disabled(disabled: bool) -> Generator[None, None, None]:
    with _override_env(_DISABLE_DIRECT_IO_ENV_VAR, "1" if disabled else "0"):
        yield


@contextlib.contextmanager
def override_checksum_disabled(disabled: bool) -> Generator[None, None, None]:
    with _override_env(_DISABLE_CHECKSUM_ENV_VAR, "1" if disabled else "0"):
        yield


@contextlib.contextmanager
def override_tile_checksum_bytes(nbytes: int) -> Generator[None, None, None]:
    with _override_env(_TILE_CHECKSUM_ENV_VAR, str(nbytes)):
        yield


@contextlib.contextmanager
def override_record_dedup_hashes(enabled: bool) -> Generator[None, None, None]:
    with _override_env(_RECORD_DEDUP_HASHES_ENV_VAR, "1" if enabled else "0"):
        yield


@contextlib.contextmanager
def override_telemetry_enabled(enabled: bool) -> Generator[None, None, None]:
    with _override_env(_TELEMETRY_ENV_VAR, "1" if enabled else "0"):
        yield


@contextlib.contextmanager
def override_journal_disabled(disabled: bool) -> Generator[None, None, None]:
    with _override_env(_DISABLE_JOURNAL_ENV_VAR, "1" if disabled else "0"):
        yield


@contextlib.contextmanager
def override_heartbeat_interval_s(seconds: float) -> Generator[None, None, None]:
    with _override_env(_HEARTBEAT_INTERVAL_ENV_VAR, str(seconds)):
        yield


@contextlib.contextmanager
def override_telemetry_dir(path: str) -> Generator[None, None, None]:
    with _override_env(_TELEMETRY_DIR_ENV_VAR, path):
        yield


@contextlib.contextmanager
def override_metrics_export(formats: Optional[str]) -> Generator[None, None, None]:
    with _override_env(_METRICS_EXPORT_ENV_VAR, formats):
        yield


@contextlib.contextmanager
def override_metrics_dir(path: Optional[str]) -> Generator[None, None, None]:
    with _override_env(_METRICS_DIR_ENV_VAR, path):
        yield


@contextlib.contextmanager
def override_history_enabled(enabled: bool) -> Generator[None, None, None]:
    with _override_env(_HISTORY_ENV_VAR, "1" if enabled else "0"):
        yield


@contextlib.contextmanager
def override_history_max_bytes(nbytes: int) -> Generator[None, None, None]:
    with _override_env(_HISTORY_MAX_BYTES_ENV_VAR, str(nbytes)):
        yield


@contextlib.contextmanager
def override_access_ledger(enabled: bool) -> Generator[None, None, None]:
    with _override_env(_ACCESS_LEDGER_ENV_VAR, "1" if enabled else "0"):
        yield


@contextlib.contextmanager
def override_access_ledger_max_bytes(nbytes: int) -> Generator[None, None, None]:
    with _override_env(_ACCESS_LEDGER_MAX_BYTES_ENV_VAR, str(nbytes)):
        yield


@contextlib.contextmanager
def override_stage_threads(n: int) -> Generator[None, None, None]:
    with _override_env(_STAGE_THREADS_ENV_VAR, str(n)):
        yield


@contextlib.contextmanager
def override_async_stage_window_bytes(nbytes: int) -> Generator[None, None, None]:
    """0 disables pipelined async staging (strict stage-all semantics)."""
    with _override_env(_ASYNC_STAGE_WINDOW_ENV_VAR, str(nbytes)):
        yield


@contextlib.contextmanager
def override_async_cow(enabled: bool) -> Generator[None, None, None]:
    with _override_env(_ASYNC_COW_ENV_VAR, "1" if enabled else "0"):
        yield


@contextlib.contextmanager
def override_flight_enabled(enabled: bool) -> Generator[None, None, None]:
    with _override_env(_FLIGHT_ENV_VAR, "1" if enabled else "0"):
        yield


@contextlib.contextmanager
def override_flight_flush_interval_s(seconds: float) -> Generator[None, None, None]:
    with _override_env(_FLIGHT_FLUSH_ENV_VAR, str(seconds)):
        yield


@contextlib.contextmanager
def override_slo_thresholds(
    rpo_s: Optional[float] = None, rto_s: Optional[float] = None
) -> Generator[None, None, None]:
    """Override the SLO breach thresholds in one scope (None leaves the
    corresponding env var untouched)."""
    with contextlib.ExitStack() as stack:
        if rpo_s is not None:
            stack.enter_context(_override_env(_SLO_RPO_ENV_VAR, str(rpo_s)))
        if rto_s is not None:
            stack.enter_context(_override_env(_SLO_RTO_ENV_VAR, str(rto_s)))
        yield


@contextlib.contextmanager
def override_slo_stream_cadence_x(factor: float) -> Generator[None, None, None]:
    with _override_env(_SLO_STREAM_CADENCE_X_ENV_VAR, str(factor)):
        yield


@contextlib.contextmanager
def override_tier_drain(enabled: bool) -> Generator[None, None, None]:
    with _override_env(_TIER_DRAIN_ENV_VAR, "1" if enabled else "0"):
        yield


@contextlib.contextmanager
def override_tier_outage(
    threshold: Optional[int] = None,
    backoff_cap_s: Optional[float] = None,
    op_deadline_s: Optional[float] = None,
    local_retention_s: Optional[float] = None,
) -> Generator[None, None, None]:
    """Override the write-back tier's outage/retention knobs in one
    scope (None leaves the corresponding env var untouched)."""
    with contextlib.ExitStack() as stack:
        if threshold is not None:
            stack.enter_context(
                _override_env(_TIER_OUTAGE_THRESHOLD_ENV_VAR, str(threshold))
            )
        if backoff_cap_s is not None:
            stack.enter_context(
                _override_env(_TIER_BACKOFF_CAP_ENV_VAR, str(backoff_cap_s))
            )
        if op_deadline_s is not None:
            stack.enter_context(
                _override_env(_TIER_OP_DEADLINE_ENV_VAR, str(op_deadline_s))
            )
        if local_retention_s is not None:
            stack.enter_context(
                _override_env(
                    _TIER_LOCAL_RETENTION_ENV_VAR, str(local_retention_s)
                )
            )
        yield


@contextlib.contextmanager
def override_compress(
    mode: Optional[str] = None,
    min_blob_bytes: Optional[int] = None,
) -> Generator[None, None, None]:
    """Override the fused-compression policy knobs in one scope (None
    leaves the corresponding env var untouched)."""
    with contextlib.ExitStack() as stack:
        if mode is not None:
            stack.enter_context(_override_env(_COMPRESS_ENV_VAR, mode))
        if min_blob_bytes is not None:
            stack.enter_context(
                _override_env(_COMPRESS_MIN_BLOB_ENV_VAR, str(min_blob_bytes))
            )
        yield


@contextlib.contextmanager
def override_barrier_timeout_s(seconds: float) -> Generator[None, None, None]:
    with _override_env(_BARRIER_TIMEOUT_ENV_VAR, str(seconds)):
        yield


@contextlib.contextmanager
def override_liveness(
    ttl_s: Optional[float] = None,
    policy: Optional[str] = None,
) -> Generator[None, None, None]:
    """Override the rank-liveness knobs in one scope (None leaves the
    corresponding env var untouched)."""
    with contextlib.ExitStack() as stack:
        if ttl_s is not None:
            stack.enter_context(
                _override_env(_LIVENESS_TTL_ENV_VAR, str(ttl_s))
            )
        if policy is not None:
            stack.enter_context(_override_env(_RANK_FAILURE_ENV_VAR, policy))
        yield


@contextlib.contextmanager
def override_job_id(job_id: Optional[str]) -> Generator[None, None, None]:
    """Pin (or with ``None``, restore the host-pid default of) the job
    identity in one scope."""
    with _override_env(_JOB_ID_ENV_VAR, job_id):
        yield


@contextlib.contextmanager
def override_fleet_dir(path: Optional[str]) -> Generator[None, None, None]:
    """Point the fleet status mirror at ``path`` (``None`` disables)."""
    with _override_env(_FLEET_DIR_ENV_VAR, path):
        yield


@contextlib.contextmanager
def override_cas(
    store_dir: Optional[str],
    grace_s: Optional[float] = None,
    lease_ttl_s: Optional[float] = None,
    remote: Optional[str] = None,
) -> Generator[None, None, None]:
    """Point the content-addressed store at ``store_dir`` (``None``
    disables) with optional gc grace / lease-TTL / remote overrides."""
    with contextlib.ExitStack() as stack:
        stack.enter_context(_override_env(_CAS_DIR_ENV_VAR, store_dir))
        if grace_s is not None:
            stack.enter_context(
                _override_env(_CAS_GRACE_ENV_VAR, str(grace_s))
            )
        if lease_ttl_s is not None:
            stack.enter_context(
                _override_env(_CAS_LEASE_TTL_ENV_VAR, str(lease_ttl_s))
            )
        if remote is not None:
            stack.enter_context(_override_env(_CAS_REMOTE_ENV_VAR, remote))
        yield


@contextlib.contextmanager
def override_probe(
    enabled: bool,
    interval_bytes: Optional[int] = None,
    probe_bytes: Optional[int] = None,
) -> Generator[None, None, None]:
    """Enable/disable in-take roofline probes, optionally overriding
    the cadence and probe size in the same scope (None leaves the
    corresponding env var untouched)."""
    with contextlib.ExitStack() as stack:
        stack.enter_context(
            _override_env(_PROBE_ENV_VAR, "1" if enabled else "0")
        )
        if interval_bytes is not None:
            stack.enter_context(
                _override_env(_PROBE_INTERVAL_ENV_VAR, str(interval_bytes))
            )
        if probe_bytes is not None:
            stack.enter_context(
                _override_env(_PROBE_BYTES_ENV_VAR, str(probe_bytes))
            )
        yield


@contextlib.contextmanager
def override_autotune(enabled: bool) -> Generator[None, None, None]:
    """Enable/disable the take/restore-begin auto-tuner reconcile."""
    with _override_env(_AUTOTUNE_ENV_VAR, "1" if enabled else "0"):
        yield
