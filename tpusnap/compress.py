"""Dtype-aware fused tile compression: codec model + sampled auto policy.

The native engine's staging hot path already makes one fused pass per
tile (clone + CRC32C + XXH64); on network-bound destinations (cloud,
virtio, the write-back tier's remote drain) the storage pipe, not the
host, is the ceiling, so a codec stage rides the same pass: a
byte-shuffle filter keyed on dtype element size (bf16/f32/f64 exponent
bytes group into near-constant planes; fp8/int8 skip the filter)
followed by LZ4 block compression, per checksum tile, preserving
tile-grain random access on the restore path.

The policy is MEASURED ON THE TAKE, not configured
(``TPUSNAP_COMPRESS=auto``, the default). Before anything is staged,
the real codec pass runs over ``SAMPLE_BYTES`` of the host bytes of the
eligible leaf staging reaches first (the largest; the sampler starts
that one leaf's copy to the host, the copy staging will use, so the
sample waits out one leaf's transfer and runs no device operation of
its own), and reads two numbers:
``sample_ratio`` r (bytes out / bytes in) and ``sample_gbps`` c (bytes
in / second, on the threads staging will use). The pipe's write ceiling
p comes from the in-take roofline probes (``TPUSNAP_PROBE=1``,
scheduler._ProbeRunner feeds every sample here) or, when no sample
exists yet and the take is large enough to amortize it, from a one-shot
policy mini-probe through the take's own plugin stack.

The rule weighs what the codec removes against what it costs. Staged
serially, as in the window ``async_take`` blocks the caller on, B raw
bytes cost B/p on the pipe; compressed they cost B/c in the codec and
r*B/p on the pipe. The codec pays when B/c + r*B/p < B/p, that is when
it takes bytes off the pipe faster than the pipe would have carried
them:

    c * (1 - r) >= COMPRESS_MARGIN * p

With staging and writes overlapped the codec can only pay when c > p,
which the rule implies (r >= 0), so the serial form is the stricter
one. The gain is bounded by 1 - r and the cost is not: full-entropy
f32 state (r ~0.93, c ~0.65 GB/s: 0.05 GB/s taken off the pipe)
bypasses on any pipe a checkpoint is written to, bf16-precision values
held in f32 (r ~0.5, c ~2 GB/s) compress under a 0.2 GB/s bucket.
Doubt means bypass: no leaf whose bytes are free to read
(``no_sample``), no ceiling, a failed sample. All checksums/dedup
hashes of a compressed blob are recorded over the STORED (compressed)
bytes, so the journal/salvage/upload-journal dual-hash evidence rule,
scrub and fsck hold unchanged.
"""

from __future__ import annotations

import logging
import threading
import time
from contextlib import nullcontext
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

logger = logging.getLogger(__name__)

# Auto mode never probes (or compresses) a take whose eligible payload
# is below this floor: a small take cannot amortize the policy probe or
# the codec bookkeeping, and bypass is within noise there anyway.
AUTO_MIN_TAKE_BYTES = 64 << 20

# Compress only when what the codec takes off the pipe clearly outruns
# the pipe: at parity the codec would serialize the take behind the CPU
# for ~zero effective gain, and both the probe ceiling and an 8 MiB
# sample carry measurement noise.
COMPRESS_MARGIN = 1.3

# The policy's codec sample: this many bytes of the take's own state,
# gathered as equal pieces spread evenly over one leaf (a padded tail
# or a zero head must not speak for the whole), each piece one tile of
# the sampling pass so that it runs on as many threads as staging's.
SAMPLE_BYTES = 8 << 20
_SAMPLE_PIECES = 4

# Policy mini-probe: streams x bytes written through the take's own
# plugin stack (PROBE_DIR namespace: journal-exempt sidecar space, a
# crash's leftovers are orphan-visible to fsck/gc).
_POLICY_PROBE_STREAMS = 2
_POLICY_PROBE_STREAM_BYTES = 4 << 20


def codec_for_dtype(dtype_str: str) -> Optional[str]:
    """The codec family for a manifest dtype string, or None when the
    dtype is not compressible (unknown/odd element sizes). Element size
    keys the byte-shuffle filter: ``shuf4+lz4`` for f32, ``shuf2+lz4``
    for bf16/f16, plain ``lz4`` for 1-byte dtypes (fp8/int8/uint8,
    where a shuffle is the identity)."""
    from .serialization import tensor_nbytes

    try:
        itemsize = tensor_nbytes(dtype_str, [1])
    except Exception:
        return None
    if itemsize == 1:
        return "lz4"
    if itemsize in (2, 4, 8):
        return f"shuf{itemsize}+lz4"
    return None


def codec_elem(codec: str) -> int:
    """Byte-shuffle element size encoded in a codec name. Raises
    ValueError for codec families this build cannot decode — the
    restore path surfaces that as a clear error instead of garbage."""
    if codec == "lz4":
        return 1
    if codec.startswith("shuf") and codec.endswith("+lz4"):
        try:
            elem = int(codec[4:-4])
        except ValueError:
            raise ValueError(f"unknown codec {codec!r}") from None
        if elem in (2, 4, 8):
            return elem
    raise ValueError(
        f"unknown codec {codec!r} — this snapshot was written by a newer "
        "build; upgrade to restore it"
    )


# ---------------------------------------------------------------- ceilings

# Process-global pipe ceilings by (storage label, lane), fed by every
# roofline probe sample (each probe measures both its write and read
# legs) and by the policy mini-probe. Lanes are "write" and "read":
# asymmetric backends (write-back tiers, read-optimized mounts) get
# separate ceilings so the restore roofline never divides by a write
# number. Newest sample wins: the probe's whole point is that the
# ceiling is a live measurement, not a config belief.
_ceilings: Dict[Tuple[str, str], float] = {}
_ceilings_lock = threading.Lock()


def pipe_ceiling_key(storage) -> str:
    """Registry key for a plugin stack's pipe ceiling: the innermost
    backend class name PLUS the device/bucket it points at, so two
    same-class backends with different bandwidth — a fast local NVMe
    dir and a slow NFS/virtio fs:// mount in one process — never share
    (and poison) one sample. Filesystem plugins key on ``st_dev`` of
    the root's nearest existing ancestor (different mounts → different
    devices; sibling snapshot dirs on one disk → one shared ceiling,
    which is the reuse the probe feed exists for); object stores key on
    their bucket."""
    import os

    from .storage_plugin import StoragePlugin, storage_plugin_label

    label = storage_plugin_label(storage)
    base = storage
    while isinstance(getattr(base, "inner", None), StoragePlugin):
        base = base.inner
    root = getattr(base, "root", None)
    if root:
        p = os.path.abspath(str(root))
        while True:
            try:
                return f"{label}@dev{os.stat(p).st_dev}"
            except OSError:
                parent = os.path.dirname(p)
                if parent == p:
                    break
                p = parent
    for attr in ("bucket", "bucket_name", "netloc"):
        v = getattr(base, attr, None)
        if v:
            return f"{label}@{v}"
    return label


def note_pipe_ceiling(label: str, gbps: float, lane: str = "write") -> None:
    if not label or gbps <= 0:
        return
    with _ceilings_lock:
        _ceilings[(label, lane)] = float(gbps)


def pipe_ceiling(label: str, lane: str = "write") -> Optional[float]:
    with _ceilings_lock:
        return _ceilings.get((label, lane))


def pipe_ceilings_snapshot() -> Dict[Tuple[str, str], float]:
    """Copy of every (label, lane) ceiling known to this process — the
    tune planner's view of what the probes have measured."""
    with _ceilings_lock:
        return dict(_ceilings)


def _reset_ceilings() -> None:
    """Test seam."""
    with _ceilings_lock:
        _ceilings.clear()


# ---------------------------------------------------------------- decision


@dataclass
class CompressDecision:
    """One take's resolved compression policy, recorded in the take's
    telemetry meta (→ summary → history event) and readable after the
    fact via ``LAST_DECISION`` (tests assert on it). The ``sample_*``
    fields are what the codec did to ``sample_bytes`` of this take's own
    state; they stay 0 where no sample was taken (forced modes, and
    every bypass decided before the sample)."""

    mode: str
    compress: bool
    reason: str
    sample_ratio: float = 0.0
    sample_gbps: float = 0.0
    sample_bytes: int = 0
    pipe_gbps: Optional[float] = None
    eligible_bytes: int = 0

    def to_meta(self) -> Dict[str, object]:
        d: Dict[str, object] = {
            "mode": self.mode,
            "decision": "compress" if self.compress else "bypass",
            "reason": self.reason,
            "sample_ratio": self.sample_ratio,
            "sample_gbps": self.sample_gbps,
            "sample_bytes": self.sample_bytes,
            "eligible_bytes": self.eligible_bytes,
        }
        if self.pipe_gbps is not None:
            d["pipe_gbps"] = round(self.pipe_gbps, 4)
        return d


LAST_DECISION: Optional[CompressDecision] = None


def _policy_probe(storage, event_loop, label: str) -> Optional[float]:
    """One-shot write ceiling measurement through the take's own plugin
    stack (the probe traffic sees the same chaos/retry/journal layers
    the take's blobs do, by design). Returns GB/s or None; the sample
    is cached in the ceiling registry either way a sample lands."""
    import os

    from .io_types import PROBE_DIR, WriteIO

    try:
        block = os.urandom(1 << 20)
        reps = _POLICY_PROBE_STREAM_BYTES // len(block)
        buf = memoryview(block * reps)
        paths = [
            f"{PROBE_DIR}/policy_{os.getpid()}_{i}.bin"
            for i in range(_POLICY_PROBE_STREAMS)
        ]
        import asyncio

        from .io_types import run_on_loop

        async def _run() -> float:
            t0 = time.monotonic()
            await asyncio.gather(
                *(storage.write(WriteIO(path=p, buf=buf)) for p in paths)
            )
            elapsed = max(time.monotonic() - t0, 1e-9)
            await asyncio.gather(
                *(storage.delete(p) for p in paths), return_exceptions=True
            )
            return len(buf) * len(paths) / elapsed / 1e9

        gbps = run_on_loop(event_loop, _run())
        note_pipe_ceiling(label, gbps)
        from . import telemetry

        telemetry.incr("compress.policy_probes")
        return gbps
    except Exception:
        logger.warning(
            "compression policy probe failed (non-fatal; bypassing)",
            exc_info=True,
        )
        return None


def _eligible_stagers(write_reqs) -> List[object]:
    """The stagers fused compression may apply to: standalone dense
    array blobs (incl. chunk blobs) above the per-blob floor, of a
    dtype the shuffle filter understands. Slab members (batched small
    arrays) and sharded shards (whose restore path reads arbitrary
    overlap sub-ranges — impossible at compressed-tile grain) are
    constructed with ``compressible=False`` and never appear here."""
    from .io_preparers.array import ArrayBufferStager
    from .knobs import get_compress_min_blob_bytes

    floor = get_compress_min_blob_bytes()
    out = []
    for wr in write_reqs:
        st = wr.buffer_stager
        if not isinstance(st, ArrayBufferStager):
            continue
        if not getattr(st, "compressible", True):
            continue
        entry = st.entry
        if entry is None or entry.byte_range is not None:
            continue
        if codec_for_dtype(entry.dtype) is None:
            continue
        if st.get_planned_bytes() < floor:
            continue
        out.append(st)
    return out


def _sample_codec(eligible, rec) -> Optional[Tuple[float, float, int]]:
    """``(ratio, gbps, bytes)`` of the real codec pass over a sample of
    this take's own bytes, or None when no eligible stager gives its
    host bytes away for free. The source is the eligible stager staging
    reaches first: the scheduler stages largest first and keeps request
    order among equals, and so does ``max``."""
    import numpy as np

    from . import _native, telemetry
    from .knobs import get_native_copy_threads
    from .serialization import array_as_memoryview

    sources = [st for st in eligible if st.host_bytes_are_free()]
    if not sources:
        return None
    st = max(sources, key=lambda s: s.get_planned_bytes())
    spans = rec is not None and rec.enabled
    t0 = rec.now() if spans else 0.0
    # The copy staging will use, started here and counted once: the
    # scheduler dispatches this request first and finds it under way.
    st.start_dtoh()
    host = st.host_array()
    if spans and not isinstance(st.arr, np.ndarray):
        # This one leaf's transfer: the wait the staging thread would
        # have paid for it. It keeps that wait's name; the leaf's bytes
        # stay with the stager's own span.
        rec.record_span(
            "dtoh", t0, rec.now() - t0, kind=telemetry.WAIT, sample=True
        )
    flat = np.frombuffer(array_as_memoryview(host), np.uint8)
    elem = codec_elem(codec_for_dtype(st.entry.dtype))
    with rec.span("compress.sample") if spans else nullcontext() as sp:
        if flat.nbytes > SAMPLE_BYTES:
            piece = SAMPLE_BYTES // _SAMPLE_PIECES
            stride = (flat.nbytes - piece) // (_SAMPLE_PIECES - 1) // elem * elem
            flat = np.concatenate(
                [flat[i * stride : i * stride + piece] for i in range(_SAMPLE_PIECES)]
            )
        else:
            piece = max(elem, flat.nbytes // _SAMPLE_PIECES // elem * elem)
        t_codec = time.monotonic()
        out = _native.compress_tiles(
            flat, piece, elem, False, nthreads=get_native_copy_threads()
        )[0]
        elapsed = max(time.monotonic() - t_codec, 1e-9)
        if sp is not None:
            sp.attrs.update(bytes=flat.nbytes, out_bytes=out.nbytes)
    return (
        round(out.nbytes / flat.nbytes, 4),
        round(flat.nbytes / elapsed / 1e9, 4),
        flat.nbytes,
    )


def apply_take_policy(write_reqs, storage, event_loop, rec=None):
    """Resolve this take's compress-or-bypass decision and arm the
    eligible stagers. Called once per take, after batching and before
    scheduling; never raises (a policy failure must not fail a take)."""
    global LAST_DECISION
    try:
        decision = _apply_take_policy_impl(write_reqs, storage, event_loop, rec)
    except Exception:
        logger.warning("compression policy failed (bypassing)", exc_info=True)
        decision = CompressDecision(
            mode="auto", compress=False, reason="policy_error"
        )
    LAST_DECISION = decision
    try:
        if rec is not None:
            rec.meta["compress"] = decision.to_meta()
        if decision.compress or decision.reason not in (
            "mode_off",
            "no_eligible_blobs",
            "below_auto_floor",
        ):
            from . import flight

            flight.record(
                "compress_policy",
                op=decision.reason,
                decision="compress" if decision.compress else "bypass",
                sample_ratio=decision.sample_ratio,
                sample_gbps=decision.sample_gbps,
                pipe_gbps=decision.pipe_gbps,
            )
    except Exception:
        logger.debug("compress decision recording failed", exc_info=True)
    return decision


def _apply_take_policy_impl(write_reqs, storage, event_loop, rec=None):
    from . import _native
    from .knobs import get_compress_mode, is_checksum_disabled

    mode = get_compress_mode()

    def bypass(reason: str, **fields) -> CompressDecision:
        return CompressDecision(mode=mode, compress=False, reason=reason, **fields)

    if mode == "off":
        return bypass("mode_off")
    if is_checksum_disabled():
        # Compressed restores verify the stored bytes by checksum; with
        # checksums off there is no integrity evidence to record.
        return bypass("checksums_disabled")
    if not _native.compression_available():
        return bypass("native_unavailable")
    eligible = _eligible_stagers(write_reqs)
    if not eligible:
        return bypass("no_eligible_blobs")
    eligible_bytes = sum(st.get_planned_bytes() for st in eligible)
    measured = {"eligible_bytes": eligible_bytes}
    if mode == "auto":
        if eligible_bytes < AUTO_MIN_TAKE_BYTES:
            return bypass("below_auto_floor", **measured)
        sample = _sample_codec(eligible, rec)
        if sample is None:
            return bypass("no_sample", **measured)
        ratio, gbps, nbytes = sample
        measured.update(sample_ratio=ratio, sample_gbps=gbps, sample_bytes=nbytes)
        label = pipe_ceiling_key(storage)
        pipe = pipe_ceiling(label)
        if pipe is None:
            pipe = _policy_probe(storage, event_loop, label)
        if pipe is None:
            return bypass("no_pipe_ceiling", **measured)
        measured["pipe_gbps"] = pipe
        if gbps * (1.0 - ratio) < pipe * COMPRESS_MARGIN:
            return bypass("pipe_outruns_codec", **measured)
        reason = "codec_outruns_pipe"
    else:
        reason = "mode_forced"
    for st in eligible:
        st.compress_codec = codec_for_dtype(st.entry.dtype)
    return CompressDecision(mode=mode, compress=True, reason=reason, **measured)


# ------------------------------------------------------- restore helpers


def check_tile_coverage(
    location: str, n_sizes: int, raw_nbytes: int, tile_raw: int
) -> None:
    """Refuse a codec entry whose comp_tile_sizes does not COVER the
    payload: per-group/whole-blob checksums of a truncated list (buggy
    external rewriter) would all verify while the destination tail is
    never written — silent garbage. Shared by the standalone and
    chunked read paths so both decoders enforce one contract."""
    if not raw_nbytes or not tile_raw:
        return
    expected_tiles = -(-raw_nbytes // tile_raw)
    if n_sizes != expected_tiles:
        raise IOError(
            f"compressed entry {location!r} records {n_sizes} tile(s) "
            f"but its {raw_nbytes}-byte payload spans {expected_tiles} "
            f"at {tile_raw} raw bytes/tile — the snapshot metadata is "
            "inconsistent"
        )


def comp_tile_offsets(comp_sizes: List[int]) -> List[int]:
    """Start offset of each compressed tile within the stored blob."""
    out = []
    off = 0
    for s in comp_sizes:
        out.append(off)
        off += int(s)
    return out


def combined_comp_checksum(entry, t0: int, t1: int) -> Optional[str]:
    """Expected checksum of compressed tiles [t0, t1) of a codec entry,
    derived from the recorded per-tile values by CRC combine over the
    COMPRESSED tile lengths — the compressed-blob counterpart of
    ``combined_tile_checksum``. None when the range is unverifiable
    (no tiles, algorithm mismatch)."""
    from . import _native

    sizes = entry.comp_tile_sizes or []
    if not entry.tile_checksums:
        if t0 == 0 and t1 == len(sizes) == 1:
            return entry.checksum
        return None
    algo = _native.checksum_algorithm()
    crcs: List[int] = []
    lengths: List[int] = []
    for i in range(t0, t1):
        tile = entry.tile_checksums[i]
        tile_algo, _, value = tile.partition(":")
        if tile_algo != algo:
            return None
        try:
            crcs.append(int(value, 16))
        except ValueError:
            return None
        lengths.append(int(sizes[i]))
    if not crcs:
        return None
    from .io_preparers.array import _fold_crcs

    return f"{algo}:{_fold_crcs(crcs, lengths):08x}"
