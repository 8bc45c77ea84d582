"""Dtype-aware fused tile compression (tpusnap/compress.py + the native
shuffle+LZ4 codec) and its probe-driven auto policy.

Covers the acceptance criteria:

- compressed takes restore bit-exact; scrub and fsck validate the
  compressed tiles (bit-rot in one compressed tile is caught and named);
- a pre-compression (uncompressed) snapshot restores bit-exact under the
  new code, and a compression-off take round-trips with no codec fields;
- chaos SIGKILL mid-compressed-take → fsck torn + a salvage-resume
  retake reuses the intact compressed blobs via the dual-hash rule;
- the write-back tiering drain uploads compressed blobs, with the lag
  gauges counting COMPRESSED bytes;
- the auto policy is measured on the take: the codec's ratio and rate on
  a sample of the take's own bytes, weighed against the recorded pipe
  ceiling (compress when rate x (1 - ratio) clearly outruns the pipe,
  bypass when it does not, when a number is missing, or when the take
  is too small to amortize the decision).
"""

import contextlib
import os
import signal
import subprocess
import sys

import numpy as np
import pytest

from tpusnap import PytreeState, Snapshot, StateDict, verify_snapshot
from tpusnap import _native, telemetry
from tpusnap import compress as compress_mod
from tpusnap.knobs import (
    override_batching_disabled,
    override_compress,
    override_max_chunk_size_bytes,
    override_memory_budget_bytes,
    override_record_dedup_hashes,
    override_tile_checksum_bytes,
)
from tpusnap.manifest import TensorEntry

needs_native = pytest.mark.skipif(
    not _native.compression_available(),
    reason="native codec unavailable (no toolchain)",
)


def _bf16ish(shape, seed=0):
    """f32 data with bf16 precision (low mantissa bytes zeroed) — the
    mixed-precision-export shape the codec targets; compresses ~2x+."""
    rng = np.random.default_rng(seed)
    a = rng.standard_normal(shape).astype(np.float32)
    return (a.view(np.uint32) & np.uint32(0xFFFF0000)).view(np.float32)


def _blob_files(root):
    out = []
    for dirpath, _, files in os.walk(root):
        if ".tpusnap" in dirpath.split(os.sep):
            continue
        for f in files:
            if f != ".snapshot_metadata":
                out.append(os.path.relpath(os.path.join(dirpath, f), root))
    return sorted(out)


def _payload_bytes(root):
    return sum(
        os.path.getsize(os.path.join(root, f)) for f in _blob_files(root)
    )


@pytest.fixture(autouse=True)
def _fresh_policy_state():
    compress_mod._reset_ceilings()
    yield
    compress_mod._reset_ceilings()
    compress_mod.LAST_DECISION = None


# ------------------------------------------------------------ native codec


@needs_native
@pytest.mark.parametrize(
    "dtype,elem",
    [(np.float32, 4), (np.float16, 2), (np.int8, 1), (np.float64, 8)],
)
def test_tile_roundtrip_across_dtypes(dtype, elem):
    rng = np.random.default_rng(11)
    if dtype is np.int8:
        arr = rng.integers(-8, 8, 300_001).astype(dtype)  # low entropy
    else:
        arr = rng.standard_normal(300_001).astype(dtype)  # odd length tail
    buf = arr.tobytes()
    tile = 1 << 16  # many tiles, short last tile
    out, sizes, crcs, xxhs = _native.compress_tiles(buf, tile, elem, True)
    assert sum(sizes) == out.nbytes
    n_tiles = (len(buf) + tile - 1) // tile
    assert len(sizes) == len(crcs) == len(xxhs) == n_tiles
    # The recorded hashes are over the STORED bytes of each tile.
    off = 0
    for i, s in enumerate(sizes):
        assert _native.crc32c(bytes(out[off : off + s])) == crcs[i]
        off += s
    dec = bytearray(len(buf))
    _native.decompress_tiles(out, sizes, tile, len(buf), elem, dec)
    assert bytes(dec) == buf


@needs_native
def test_incompressible_tiles_stored_raw():
    """Random bytes do not shrink: every tile stores raw (comp size ==
    raw tile size — the decoder's unambiguous marker) and the total
    never exceeds the input."""
    buf = np.random.default_rng(1).integers(0, 255, 1 << 18, dtype=np.uint8)
    buf = buf.tobytes()
    tile = 1 << 16
    out, sizes, _, _ = _native.compress_tiles(buf, tile, 1, False)
    assert out.nbytes == len(buf)
    assert all(s == tile for s in sizes)
    dec = bytearray(len(buf))
    _native.decompress_tiles(out, sizes, tile, len(buf), 1, dec)
    assert bytes(dec) == buf


@needs_native
def test_codec_is_deterministic():
    """Equal input bytes always yield equal stored bytes — the property
    incremental dedup and salvage-resume rest on."""
    buf = _bf16ish((512, 128)).tobytes()
    a, sa, ca, xa = _native.compress_tiles(buf, 1 << 16, 4, True, nthreads=4)
    b, sb, cb, xb = _native.compress_tiles(buf, 1 << 16, 4, True, nthreads=1)
    assert bytes(a) == bytes(b) and sa == sb and ca == cb and xa == xb


@needs_native
def test_python_fallback_decode_matches_native():
    """The pure-Python LZ4+unshuffle decoder (TPUSNAP_DISABLE_NATIVE
    restores) decodes native-compressed tiles bit-exactly."""
    arr = _bf16ish((300, 77), seed=5)
    buf = arr.tobytes()
    tile = 1 << 14
    out, sizes, _, _ = _native.compress_tiles(buf, tile, 4, False)
    dec = bytearray(len(buf))
    _native._py_decompress_tiles(
        memoryview(bytes(out)), sizes, tile, len(buf), 4, memoryview(dec)
    )
    assert bytes(dec) == buf


@needs_native
def test_malformed_compressed_input_raises_cleanly():
    buf = _bf16ish((256, 64)).tobytes()
    out, sizes, _, _ = _native.compress_tiles(buf, len(buf), 4, False)
    assert out.nbytes < len(buf)
    # Truncated stream, garbage stream, wrong sizes: CompressionError,
    # never OOB writes or hangs — in BOTH decoders.
    for decoder in ("native", "python"):

        def dec(src, szs):
            o = bytearray(len(buf))
            if decoder == "native":
                _native.decompress_tiles(src, szs, len(buf), len(buf), 4, o)
            else:
                _native._py_decompress_tiles(
                    memoryview(bytes(src)), szs, len(buf), len(buf), 4,
                    memoryview(o),
                )

        with pytest.raises(_native.CompressionError):
            dec(out[: out.nbytes // 2], [out.nbytes // 2])
        garbage = np.frombuffer(os.urandom(out.nbytes), dtype=np.uint8)
        with pytest.raises(_native.CompressionError):
            dec(garbage, sizes)
        with pytest.raises(_native.CompressionError):
            dec(out, [out.nbytes + 7])


# ------------------------------------------------------------- policy units


def test_codec_for_dtype_mapping():
    assert compress_mod.codec_for_dtype("float32") == "shuf4+lz4"
    assert compress_mod.codec_for_dtype("bfloat16") == "shuf2+lz4"
    assert compress_mod.codec_for_dtype("float16") == "shuf2+lz4"
    assert compress_mod.codec_for_dtype("float64") == "shuf8+lz4"
    assert compress_mod.codec_for_dtype("int8") == "lz4"
    assert compress_mod.codec_for_dtype("no_such_dtype") is None
    assert compress_mod.codec_elem("shuf4+lz4") == 4
    assert compress_mod.codec_elem("lz4") == 1
    with pytest.raises(ValueError, match="newer"):
        compress_mod.codec_elem("zstd19")  # future codec: loud refusal


def _mk_reqs(nbytes=1 << 20, dtype=np.float32, arr=None, **stager_kwargs):
    """One real ArrayBufferStager-backed write request, policy-eligible."""
    from tpusnap.io_preparers.array import ArrayBufferStager
    from tpusnap.io_types import WriteReq
    from tpusnap.serialization import dtype_to_string

    if arr is None:
        arr = np.zeros(nbytes // np.dtype(dtype).itemsize, dtype=dtype)
    entry = TensorEntry(
        location="0/w",
        serializer="buffer_protocol",
        dtype=dtype_to_string(arr.dtype),
        shape=list(arr.shape),
        replicated=False,
    )
    stager = ArrayBufferStager(
        arr, is_async_snapshot=False, entry=entry, **stager_kwargs
    )
    return [WriteReq(path="0/w", buffer_stager=stager)], stager


def _full_entropy(shape, seed=0):
    """f32 values with every mantissa bit in use: trained f32 state. The
    codec removes ~7 % (the exponent plane), as on the chip's cells."""
    return np.random.default_rng(seed).standard_normal(shape).astype(np.float32)


def _pin_sample_rate(monkeypatch, gbps):
    """The sample's ratio stays what the codec measured on the data; its
    rate, which is this host's, is pinned so that a case means the same
    on every machine."""
    real = compress_mod._sample_codec

    def pinned(eligible, rec):
        sample = real(eligible, rec)
        return None if sample is None else (sample[0], gbps, sample[2])

    monkeypatch.setattr(compress_mod, "_sample_codec", pinned)


_FULL, _BF16ISH = "full_entropy_f32", "bf16_precision_f32"

# (data, the codec's rate on it in GB/s, pipe GB/s, compress?). The rates
# are the chip host's: 0.65 GB/s on full-entropy f32 state, ~2 GB/s on
# bf16-precision values, whose zeroed planes LZ4 skips over.
_MATRIX = [
    # 0.65 x (1 - 0.93) = 0.045 GB/s taken off the pipe: under 1.3 x any
    # pipe a checkpoint is written to.
    (_FULL, 0.65, 0.05, False),
    (_FULL, 0.65, 0.2, False),
    (_FULL, 0.65, 1.0, False),
    (_FULL, 0.65, 10.0, False),
    # 2.0 x (1 - 0.43) = 1.14 GB/s: a bucket loses, local NVMe wins.
    (_BF16ISH, 2.0, 0.2, True),
    (_BF16ISH, 2.0, 10.0, False),
    # The margin: 1.14 against 1.3 x pipe turns between 0.8 and 0.95.
    (_BF16ISH, 2.0, 0.8, True),
    (_BF16ISH, 2.0, 0.95, False),
    # A codec as slow as the pipe gains nothing whatever the ratio.
    (_BF16ISH, 0.2, 0.2, False),
]


@needs_native
@pytest.mark.parametrize(
    "data,rate,pipe,want",
    _MATRIX,
    ids=[f"{d}-codec{r}-pipe{p}" for d, r, p, _ in _MATRIX],
)
def test_auto_policy_decision_matrix(monkeypatch, data, rate, pipe, want):
    monkeypatch.setattr(compress_mod, "AUTO_MIN_TAKE_BYTES", 1 << 18)
    monkeypatch.setattr(compress_mod, "pipe_ceiling", lambda label: pipe)
    _pin_sample_rate(monkeypatch, rate)
    make = _full_entropy if data == _FULL else _bf16ish
    reqs, st = _mk_reqs(arr=make(1 << 20))
    with override_compress(mode="auto", min_blob_bytes=65536):
        d = compress_mod.apply_take_policy(reqs, None, None)
    assert (d.compress, d.reason) == (
        (True, "codec_outruns_pipe") if want else (False, "pipe_outruns_codec")
    )
    assert st.compress_codec == ("shuf4+lz4" if want else None)
    assert d.pipe_gbps == pipe and d.sample_gbps == rate
    assert d.sample_bytes == 4 << 20  # the whole leaf: it is under the cap
    if data == _FULL:
        assert 0.9 < d.sample_ratio < 0.97
    else:
        assert 0.3 < d.sample_ratio < 0.5
    # The rule, from the decision's own numbers.
    assert want == (
        d.sample_gbps * (1 - d.sample_ratio)
        >= compress_mod.COMPRESS_MARGIN * d.pipe_gbps
    )


@needs_native
def test_auto_policy_below_floor_takes_no_sample(monkeypatch):
    monkeypatch.setattr(compress_mod, "AUTO_MIN_TAKE_BYTES", 1 << 18)
    monkeypatch.setattr(compress_mod, "_sample_codec", _must_not_sample)
    # Below the auto floor: bypass without consulting sample or ceiling.
    reqs, st = _mk_reqs(nbytes=1 << 17)
    with override_compress(mode="auto", min_blob_bytes=65536):
        d = compress_mod.apply_take_policy(reqs, None, None)
    assert (d.compress, d.reason) == (False, "below_auto_floor")
    assert d.sample_bytes == 0


def _must_not_sample(eligible, rec):
    raise AssertionError("this decision takes no sample")


@needs_native
def test_sample_spreads_over_a_large_leaf_and_caps_its_bytes():
    """A leaf over the cap is sampled in pieces spread from its head to
    its tail: a leaf whose first half is zeros reads half compressible,
    not all or nothing."""
    n = (64 << 20) // 4
    arr = np.random.default_rng(3).integers(0, 2**32, n, dtype=np.uint32).view(
        np.float32
    )
    arr[: n // 2] = 0.0
    _, st = _mk_reqs(arr=arr)
    ratio, gbps, nbytes = compress_mod._sample_codec([st], None)
    assert nbytes == compress_mod.SAMPLE_BYTES
    assert 0.45 < ratio < 0.55 and gbps > 0


def _ceiling_label(root):
    """The registry key of a take under ``root``: noting a ceiling there
    is what an earlier probe of that mount would have done."""
    import asyncio

    from tpusnap.storage_plugin import url_to_storage_plugin_in_event_loop

    loop = asyncio.new_event_loop()
    storage = url_to_storage_plugin_in_event_loop(str(root), loop)
    try:
        return compress_mod.pipe_ceiling_key(storage)
    finally:
        storage.sync_close(loop)
        loop.close()


@needs_native
def test_two_takes_over_different_data_decide_differently(tmp_path):
    """Nothing is carried from a synthetic buffer or from an earlier
    take: under one pipe ceiling, a take of incompressible state stores
    raw and the next take in the process, of bf16-precision state,
    compresses; and back."""
    label = _ceiling_label(tmp_path)
    noise = np.random.default_rng(5).integers(
        0, 2**32, (4096, 4096), dtype=np.uint32
    ).view(np.float32)
    smooth = _bf16ish((4096, 4096), seed=6)
    seen = []
    for i, arr in enumerate((noise, smooth, noise)):
        # A slow bucket's ceiling, read by every take (no mini-probe).
        compress_mod.note_pipe_ceiling(label, 0.01)
        path = str(tmp_path / f"snap{i}")
        with override_compress(mode="auto"), override_batching_disabled(True):
            Snapshot.take(path, {"app": StateDict(w=arr.copy())})
        d = compress_mod.LAST_DECISION
        seen.append((d.compress, d.reason))
        assert d.sample_bytes == compress_mod.SAMPLE_BYTES and d.pipe_gbps == 0.01
        assert (_payload_bytes(path) < arr.nbytes) == d.compress
        out = {"app": StateDict(w=np.zeros_like(arr))}
        Snapshot(path).restore(out)
        assert np.array_equal(out["app"]["w"].view(np.uint32), arr.view(np.uint32))
    assert seen == [
        (False, "pipe_outruns_codec"),
        (True, "codec_outruns_pipe"),
        (False, "pipe_outruns_codec"),
    ]


class _SpanSink(telemetry.MetricsSink):
    def __init__(self):
        self.records = []

    def on_span_record(self, record):
        self.records.append(record)


@contextlib.contextmanager
def _compile_events():
    """Every lowering, compile or cache fetch JAX reports inside the
    block (the events the benchmark allows none of inside its window)."""
    import jax

    events = []

    def listener(event, duration, **kw):
        if "compil" in event:
            events.append(event)

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        yield events
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)


@needs_native
def test_sample_reads_a_jax_leaf_without_a_device_operation(monkeypatch):
    """The sample's bytes are the host copy that the sampler starts for
    its one source, the copy staging then uses (counted once): between
    the stager's construction and the decision nothing is lowered or
    compiled, and the stager's own ``dtoh`` span (with the leaf's
    bytes) follows the sample's."""
    import jax.numpy as jnp

    monkeypatch.setattr(compress_mod, "AUTO_MIN_TAKE_BYTES", 1 << 18)
    monkeypatch.setattr(compress_mod, "pipe_ceiling_key", lambda storage: "X")
    compress_mod.note_pipe_ceiling("X", 0.001)
    leaf = jnp.asarray(_bf16ish((1024, 1024), seed=8))
    with _compile_events() as heard:
        (leaf * 2).block_until_ready()
    assert heard  # the listener does hear this backend
    rec = telemetry.TakeTelemetry(rank=0, enabled=True)
    with telemetry.metrics_sink(_SpanSink()) as sink, _compile_events() as compiles:
        reqs, st = _mk_reqs(arr=leaf)  # prepare: no copy is started yet
        assert st.dtoh_started is None and st.host_bytes_are_free()
        before = telemetry.counter_value("dtoh.enqueued_bytes")
        with override_compress(mode="auto", min_blob_bytes=65536):
            d = compress_mod.apply_take_policy(reqs, None, None, rec=rec)
        assert d.compress and d.sample_bytes == leaf.nbytes
        assert st.dtoh_started is not None  # the sampler started it
        assert st.start_dtoh() == leaf.nbytes  # the scheduler's call: no second copy
        assert telemetry.counter_value("dtoh.enqueued_bytes") - before == leaf.nbytes
        with telemetry.use(rec):
            staged = st._stage_blocking()
    assert compiles == []
    assert memoryview(staged).nbytes < leaf.nbytes  # the compressed blob
    spans = [r for r in sink.records if r.name in ("dtoh", "compress.sample")]
    assert [r.name for r in spans] == ["dtoh", "compress.sample", "dtoh"]
    wait, sample, own = spans
    assert wait.kind == telemetry.WAIT and "bytes" not in wait.attrs
    assert sample.kind == telemetry.WORK and sample.attrs["bytes"] == leaf.nbytes
    assert 0 < sample.attrs["out_bytes"] < leaf.nbytes
    assert own.attrs["bytes"] == leaf.nbytes and own.start >= sample.end
    meta = rec.meta["compress"]
    assert meta["sample_bytes"] == leaf.nbytes and meta["sample_gbps"] > 0
    assert 0.3 < meta["sample_ratio"] < 0.5 and meta["pipe_gbps"] == 0.001
    rec.finalize()


@needs_native
def test_no_sample_source_bypasses_and_says_so(monkeypatch):
    """A leaf behind an ``array_prepare_func`` stages another array's
    bytes, and they are not to be had before staging: a take with only
    such leaves decides bypass, reason ``no_sample``, and probes no pipe."""
    monkeypatch.setattr(compress_mod, "AUTO_MIN_TAKE_BYTES", 1 << 18)
    monkeypatch.setattr(compress_mod, "_policy_probe", _must_not_sample)
    calls = []

    def prepare(arr, tracing):
        calls.append(tracing)
        return arr

    reqs, st = _mk_reqs(arr=_bf16ish(1 << 20), array_prepare_func=prepare)
    assert not st.host_bytes_are_free()
    rec = telemetry.TakeTelemetry(rank=0, enabled=False)
    with override_compress(mode="auto", min_blob_bytes=65536):
        d = compress_mod.apply_take_policy(reqs, None, None, rec=rec)
    assert (d.compress, d.reason) == (False, "no_sample")
    assert st.compress_codec is None and not calls
    assert rec.meta["compress"]["reason"] == "no_sample"
    assert rec.meta["compress"]["sample_bytes"] == 0
    # One free leaf among them is enough, and it is the one sampled.
    free_reqs, free = _mk_reqs(arr=np.zeros(1 << 18, np.float32))
    compress_mod.note_pipe_ceiling("X", 0.001)
    monkeypatch.setattr(compress_mod, "pipe_ceiling_key", lambda s: "X")
    with override_compress(mode="auto", min_blob_bytes=65536):
        d = compress_mod.apply_take_policy(reqs + free_reqs, None, None)
    assert d.compress and d.sample_bytes == 1 << 20 and not calls
    assert st.compress_codec == free.compress_codec == "shuf4+lz4"


@needs_native
def test_forced_modes_and_eligibility(monkeypatch):
    # Forced modes decide without a sample and without a ceiling.
    monkeypatch.setattr(compress_mod, "_sample_codec", _must_not_sample)
    monkeypatch.setattr(compress_mod, "_policy_probe", _must_not_sample)
    # off: never compresses.
    reqs, st = _mk_reqs()
    with override_compress(mode="off"):
        d = compress_mod.apply_take_policy(reqs, None, None)
    assert (d.compress, d.reason) == (False, "mode_off")
    # on: compresses without a ceiling.
    reqs, st = _mk_reqs()
    with override_compress(mode="on", min_blob_bytes=65536):
        d = compress_mod.apply_take_policy(reqs, None, None)
    assert (d.compress, d.reason) == (True, "mode_forced")
    assert d.sample_bytes == 0 and d.to_meta()["sample_gbps"] == 0.0
    # Below the per-blob floor: not eligible even when forced.
    reqs, st = _mk_reqs(nbytes=1 << 17)
    with override_compress(mode="on", min_blob_bytes=1 << 20):
        d = compress_mod.apply_take_policy(reqs, None, None)
    assert (d.compress, d.reason) == (False, "no_eligible_blobs")
    # compressible=False (sharded shards): constructed out.
    reqs, st = _mk_reqs()
    st.compressible = False
    with override_compress(mode="on", min_blob_bytes=65536):
        d = compress_mod.apply_take_policy(reqs, None, None)
    assert d.reason == "no_eligible_blobs"


@needs_native
@pytest.mark.parametrize("mode", ["on", "lz4"])
def test_forced_mode_take_is_bit_exact_without_a_sample(tmp_path, monkeypatch, mode):
    """``on``/``lz4`` compress whatever the data and the pipe: even
    full-entropy state on a fast local disk, which ``auto`` stores raw."""
    monkeypatch.setattr(compress_mod, "_sample_codec", _must_not_sample)
    a = _full_entropy((2048, 512), seed=9)
    path = str(tmp_path / "snap")
    with override_compress(mode=mode, min_blob_bytes=65536), \
            override_batching_disabled(True):
        Snapshot.take(path, {"app": StateDict(w=a.copy())})
    d = compress_mod.LAST_DECISION
    assert (d.mode, d.compress, d.reason, d.sample_bytes) == (mode, True, "mode_forced", 0)
    assert _payload_bytes(path) < a.nbytes
    out = {"app": StateDict(w=np.zeros_like(a))}
    Snapshot(path).restore(out)
    assert np.array_equal(out["app"]["w"].view(np.uint32), a.view(np.uint32))


@needs_native
@pytest.mark.parametrize("durable", ["0", "1"], ids=["plain", "durable_commit"])
def test_policy_mini_probe_measures_and_cleans_up(tmp_path, monkeypatch, durable):
    """auto with no recorded ceiling: the one-shot mini-probe measures
    through the take's own plugin stack (under ``TPUSNAP_DURABLE_COMMIT``
    that is a second executor trip and an fsync a blob), caches the
    ceiling, and leaves no probe files behind. The decision weighs this
    take's sample against it, and the next take in the process samples
    again but probes no more."""
    import asyncio

    from tpusnap.storage_plugin import url_to_storage_plugin_in_event_loop

    monkeypatch.setenv("TPUSNAP_DURABLE_COMMIT", durable)
    monkeypatch.setattr(compress_mod, "AUTO_MIN_TAKE_BYTES", 1 << 18)
    loop = asyncio.new_event_loop()
    storage = url_to_storage_plugin_in_event_loop(str(tmp_path), loop)
    try:
        # Device-scoped registry key (two same-class backends on
        # different mounts must not share a ceiling sample).
        label = compress_mod.pipe_ceiling_key(storage)
        assert "@" in label
        compress_mod._reset_ceilings()
        assert compress_mod.pipe_ceiling(label) is None
        probes = telemetry.counter_value("compress.policy_probes")
        reqs, _ = _mk_reqs(arr=_full_entropy(1 << 20))
        with override_compress(mode="auto", min_blob_bytes=65536):
            d = compress_mod.apply_take_policy(reqs, storage, loop)
        assert d.reason in ("codec_outruns_pipe", "pipe_outruns_codec")
        assert d.pipe_gbps and d.pipe_gbps > 0
        assert compress_mod.pipe_ceiling(label) == pytest.approx(
            d.pipe_gbps, rel=1e-3
        )
        assert d.sample_bytes == 4 << 20 and 0.9 < d.sample_ratio < 0.97
        assert d.compress == (
            d.sample_gbps * (1 - d.sample_ratio)
            >= compress_mod.COMPRESS_MARGIN * d.pipe_gbps
        )
        assert telemetry.counter_value("compress.policy_probes") == probes + 1
        assert not os.path.exists(str(tmp_path / ".tpusnap" / "probe")) or (
            os.listdir(str(tmp_path / ".tpusnap" / "probe")) == []
        )
        # The second take: the registry's ceiling, a sample of its own.
        reqs, _ = _mk_reqs(arr=_bf16ish(1 << 20))
        with override_compress(mode="auto", min_blob_bytes=65536):
            d2 = compress_mod.apply_take_policy(reqs, storage, loop)
        assert telemetry.counter_value("compress.policy_probes") == probes + 1
        assert d2.pipe_gbps == pytest.approx(d.pipe_gbps, rel=1e-3)
        assert 0.3 < d2.sample_ratio < 0.5
    finally:
        storage.sync_close(loop)
        loop.close()


def test_unknown_mode_warns_and_falls_back(monkeypatch):
    from tpusnap.knobs import get_compress_mode

    monkeypatch.setenv("TPUSNAP_COMPRESS", "zstd-max")
    assert get_compress_mode() == "auto"


# ----------------------------------------------------------- end to end


@needs_native
def test_take_scrub_restore_roundtrip(tmp_path):
    """Forced compression: the stored payload shrinks, the manifest
    carries the codec fields, scrub verifies the compressed tiles, and
    the restore is bit-exact (f32 shuffle codec + int8 plain LZ4)."""
    a = _bf16ish((2048, 256))
    b = np.random.default_rng(2).integers(-4, 4, (512, 512)).astype(np.int8)
    path = str(tmp_path / "snap")
    with override_compress(
        mode="on", min_blob_bytes=65536
    ), override_batching_disabled(True):
        snap = Snapshot.take(path, {"app": StateDict(w=a.copy(), q=b.copy())})
    d = compress_mod.LAST_DECISION
    assert d is not None and d.compress and d.mode == "on"
    assert _payload_bytes(path) < (a.nbytes + b.nbytes) * 0.8
    md = Snapshot(path).metadata
    entry = md.manifest["0/app/w"]
    assert entry.codec == "shuf4+lz4"
    assert entry.uncompressed_nbytes == a.nbytes
    assert sum(entry.comp_tile_sizes) == os.path.getsize(
        os.path.join(path, "0/app/w")
    )
    assert md.manifest["0/app/q"].codec == "lz4"
    rep = snap.verify()
    assert rep.clean and rep.corrupt == 0 and rep.ok > 0
    tgt = {"app": StateDict(w=np.zeros_like(a), q=np.zeros_like(b))}
    Snapshot(path).restore(tgt)
    assert np.array_equal(tgt["app"]["w"], a)
    assert np.array_equal(tgt["app"]["q"], b)


@needs_native
def test_tiled_budget_restore_and_read_object(tmp_path):
    """Small checksum tiles + a small memory budget: the restore reads
    compressed tile groups under the budget, and read_object random
    access works at tile grain."""
    a = _bf16ish((4096, 64), seed=9)  # 1 MiB, 16 tiles of 64 KiB raw
    path = str(tmp_path / "snap")
    with override_compress(
        mode="on", min_blob_bytes=65536
    ), override_batching_disabled(True), override_tile_checksum_bytes(1 << 16):
        Snapshot.take(path, {"app": StateDict(w=a.copy())})
    entry = Snapshot(path).metadata.manifest["0/app/w"]
    assert entry.codec and len(entry.comp_tile_sizes) == 16
    assert len(entry.tile_checksums) == 16
    got = Snapshot(path).read_object(
        "0/app/w", memory_budget_bytes=1 << 17
    )
    assert np.array_equal(got, a)
    tgt = {"app": StateDict(w=np.zeros_like(a))}
    with override_memory_budget_bytes(1 << 17):
        Snapshot(path).restore(tgt)
    assert np.array_equal(tgt["app"]["w"], a)


@needs_native
def test_truncated_comp_tile_sizes_refused(tmp_path):
    """A codec entry whose comp_tile_sizes under-covers the payload
    (buggy external rewriter) must REFUSE to restore: every per-group
    checksum of a truncated list would verify, leaving the destination
    tail silently unwritten."""
    from concurrent.futures import Future

    from tpusnap.io_preparers.array import ArrayIOPreparer

    a = _bf16ish((4096, 64), seed=5)
    path = str(tmp_path / "snap")
    with override_compress(
        mode="on", min_blob_bytes=65536
    ), override_batching_disabled(True), override_tile_checksum_bytes(1 << 16):
        Snapshot.take(path, {"app": StateDict(w=a.copy())})
    entry = Snapshot(path).metadata.manifest["0/app/w"]
    assert len(entry.comp_tile_sizes) == 16
    entry.comp_tile_sizes = entry.comp_tile_sizes[:-2]  # rewriter bug
    with pytest.raises(IOError, match="spans 16"):
        ArrayIOPreparer._prepare_compressed_read(entry, None, None, Future())


@needs_native
def test_bitrot_in_compressed_tile_caught_and_named(tmp_path):
    """Flip one byte inside one compressed tile: scrub names the tile,
    restore refuses with a checksum error — bit-rot never decodes to
    silently wrong values."""
    a = _bf16ish((4096, 64), seed=3)
    path = str(tmp_path / "snap")
    with override_compress(
        mode="on", min_blob_bytes=65536
    ), override_batching_disabled(True), override_tile_checksum_bytes(1 << 16):
        Snapshot.take(path, {"app": StateDict(w=a.copy())})
    blob = os.path.join(path, "0/app/w")
    with open(blob, "r+b") as f:
        f.seek(os.path.getsize(blob) // 2)
        c = f.read(1)
        f.seek(-1, os.SEEK_CUR)
        f.write(bytes([c[0] ^ 0xFF]))
    rep = verify_snapshot(path)
    assert not rep.clean and rep.corrupt == 1
    assert "comp tile" in rep.failures[0].detail
    with pytest.raises(Exception, match="hecksum|orrupt"):
        Snapshot(path).restore({"app": StateDict(w=np.zeros_like(a))})


@needs_native
def test_compression_off_snapshot_roundtrips_without_codec_fields(tmp_path):
    """TPUSNAP_COMPRESS=off writes the pre-compression format exactly:
    no codec fields anywhere (the cross-version guarantee — a pre-14
    snapshot IS a compression-off snapshot), and it restores bit-exact
    under the codec-aware reader."""
    import json

    a = _bf16ish((1024, 256), seed=7)
    path = str(tmp_path / "snap")
    with override_compress(mode="off"), override_batching_disabled(True):
        Snapshot.take(path, {"app": StateDict(w=a.copy())})
    assert compress_mod.LAST_DECISION.reason == "mode_off"
    raw = open(os.path.join(path, ".snapshot_metadata"), "rb").read()
    assert b'"codec"' not in raw and b"comp_tile_sizes" not in raw.replace(
        b" ", b""
    )
    md = json.loads(raw)
    entry = md["manifest"]["0/app/w"]
    assert "codec" not in entry and "uncompressed_nbytes" not in entry
    assert _payload_bytes(path) == a.nbytes
    tgt = {"app": StateDict(w=np.zeros_like(a))}
    Snapshot(path).restore(tgt)
    assert np.array_equal(tgt["app"]["w"], a)
    assert verify_snapshot(path).clean


@needs_native
def test_chunked_array_compresses_per_chunk(tmp_path):
    """An array above the max-chunk bound: each chunk blob compresses
    independently and the chunked restore decodes into its rows."""
    a = _bf16ish((4096, 64), seed=4)  # 1 MiB
    path = str(tmp_path / "snap")
    with override_compress(
        mode="on", min_blob_bytes=65536
    ), override_batching_disabled(True), override_max_chunk_size_bytes(
        1 << 18
    ):
        Snapshot.take(path, {"app": StateDict(w=a.copy())})
    from tpusnap.manifest import ChunkedTensorEntry

    entry = Snapshot(path).metadata.manifest["0/app/w"]
    assert isinstance(entry, ChunkedTensorEntry) and len(entry.chunks) == 4
    assert all(c.tensor.codec == "shuf4+lz4" for c in entry.chunks)
    assert _payload_bytes(path) < a.nbytes * 0.8
    assert verify_snapshot(path).clean
    tgt = {"app": StateDict(w=np.zeros_like(a))}
    Snapshot(path).restore(tgt)
    assert np.array_equal(tgt["app"]["w"], a)


@needs_native
def test_async_take_compressed_skips_cow_and_clone(tmp_path):
    """Async takes: the compressed buffer is fresh memory — no defensive
    clone, no COW write-time verify — so mutating after wait_staged()
    commits the pre-mutation bytes in the DEFAULT staging mode."""
    a = _bf16ish((2048, 256), seed=6)
    orig = a.copy()
    path = str(tmp_path / "snap")
    with override_compress(
        mode="on", min_blob_bytes=65536
    ), override_batching_disabled(True):
        pending = Snapshot.async_take(path, {"app": StateDict(w=a)})
        assert pending.wait_staged(timeout=60)
        a[:] = -1.0
        pending.wait()
    summary = telemetry.LAST_TAKE_SUMMARY
    assert summary["stages"].get("cow_verify") is None
    tgt = {"app": StateDict(w=np.zeros_like(a))}
    Snapshot(path).restore(tgt)
    assert np.array_equal(tgt["app"]["w"], orig)


@needs_native
def test_incremental_dedup_over_compressed_bytes(tmp_path):
    """Unchanged arrays dedup against a compressed base at whole-blob
    grain (deterministic codec: equal input ⇒ equal stored hashes); a
    RAW base conservatively rewrites (codec is part of the identity)."""
    a = _bf16ish((1024, 256), seed=8)
    b = _bf16ish((1024, 256), seed=9)
    base, inc, inc2 = (
        str(tmp_path / "s0"), str(tmp_path / "s1"), str(tmp_path / "s2"),
    )
    with override_compress(
        mode="on", min_blob_bytes=65536
    ), override_batching_disabled(True), override_record_dedup_hashes(True):
        Snapshot.take(base, {"app": StateDict(x=a.copy(), y=b.copy())})
        # Unchanged state: both blobs skip.
        Snapshot.take(
            inc, {"app": StateDict(x=a.copy(), y=b.copy())},
            incremental_from=base,
        )
        assert _blob_files(inc) == []
        # One changed leaf: exactly one compressed blob rewrites.
        b2 = b.copy()
        b2[0, 0] += 1.0
        Snapshot.take(
            inc2, {"app": StateDict(x=a.copy(), y=b2)}, incremental_from=inc
        )
    assert _blob_files(inc2) == ["0/app/y"]
    md = Snapshot(inc2).metadata
    assert md.manifest["0/app/x"].location.startswith("../")
    tgt = {"app": StateDict(x=np.zeros_like(a), y=np.zeros_like(b))}
    Snapshot(inc2).restore(tgt)
    assert np.array_equal(tgt["app"]["x"], a)
    assert np.array_equal(tgt["app"]["y"], b2)

    # Raw base → compressed increment: no skip (identity mismatch).
    raw_base, c_inc = str(tmp_path / "r0"), str(tmp_path / "r1")
    with override_batching_disabled(True), override_record_dedup_hashes(True):
        with override_compress(mode="off"):
            Snapshot.take(raw_base, {"app": StateDict(x=a.copy())})
        with override_compress(mode="on", min_blob_bytes=65536):
            Snapshot.take(
                c_inc, {"app": StateDict(x=a.copy())},
                incremental_from=raw_base,
            )
    assert _blob_files(c_inc) == ["0/app/x"]
    assert verify_snapshot(c_inc).clean


@needs_native
def test_unchanged_compressed_blob_skips_the_codec_pass(tmp_path):
    """The raw-hash fast path: an unchanged blob deduping against a
    compressed base costs a hash pass, NOT a codec pass (a frozen model
    must not re-compress per micro-commit to write zero bytes). The
    base records uncompressed_dedup_hash; the increment's skip adopts
    the base's stored representation wholesale and still restores
    bit-exact."""
    a = _bf16ish((1024, 256), seed=12)
    base, inc = str(tmp_path / "s0"), str(tmp_path / "s1")
    with override_compress(
        mode="on", min_blob_bytes=65536
    ), override_batching_disabled(True), override_record_dedup_hashes(True):
        Snapshot.take(base, {"app": StateDict(x=a.copy())})
        assert Snapshot(base).metadata.manifest[
            "0/app/x"
        ].uncompressed_dedup_hash
        bytes_in_before = telemetry.counter_value("compress.bytes_in")
        skips_before = telemetry.counter_value("compress.raw_dedup_skips")
        Snapshot.take(
            inc, {"app": StateDict(x=a.copy())}, incremental_from=base
        )
    assert _blob_files(inc) == []
    assert telemetry.counter_value("compress.bytes_in") == bytes_in_before
    assert telemetry.counter_value("compress.raw_dedup_skips") == (
        skips_before + 1
    )
    e = Snapshot(inc).metadata.manifest["0/app/x"]
    assert e.codec and e.comp_tile_sizes and e.uncompressed_dedup_hash
    tgt = {"app": StateDict(x=np.zeros_like(a))}
    Snapshot(inc).restore(tgt)
    assert np.array_equal(tgt["app"]["x"], a)


@needs_native
def test_materialize_carries_compressed_blobs(tmp_path):
    """materialize copies a compressed base blob verbatim: the codec
    fields travel with the entry and the copied range verifies against
    the stored-bytes checksums."""
    a = _bf16ish((1024, 256), seed=12)
    base, inc = str(tmp_path / "s0"), str(tmp_path / "s1")
    with override_compress(
        mode="on", min_blob_bytes=65536
    ), override_batching_disabled(True), override_record_dedup_hashes(True):
        Snapshot.take(base, {"app": StateDict(x=a.copy())})
        Snapshot.take(inc, {"app": StateDict(x=a.copy())},
                      incremental_from=base)
    assert _blob_files(inc) == []
    stats = Snapshot(inc).materialize()
    assert stats["blobs_copied"] == 1
    import shutil

    shutil.rmtree(base)
    assert verify_snapshot(inc).clean
    tgt = {"app": StateDict(x=np.zeros_like(a))}
    Snapshot(inc).restore(tgt)
    assert np.array_equal(tgt["app"]["x"], a)
    assert Snapshot(inc).metadata.manifest["0/app/x"].codec == "shuf4+lz4"


# ------------------------------------------------------ crash + salvage

_COMPRESSED_CRASH_CHILD = r"""
import os, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["TPUSNAP_COMPRESS"] = "on"
os.environ["TPUSNAP_COMPRESS_MIN_BLOB_BYTES"] = "65536"
os.environ["TPUSNAP_DISABLE_BATCHING"] = "1"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from tpusnap import Snapshot, StateDict

path, crash_at = sys.argv[1], int(sys.argv[2])
rng = np.random.default_rng(0)
state = {}
for i in range(10):
    a = rng.standard_normal((256, 256)).astype(np.float32)
    state[f"w{i}"] = (a.view(np.uint32) & np.uint32(0xFFFF0000)).view(np.float32)
Snapshot.take(
    "chaos+fs://" + path,
    {"app": StateDict(**state)},
    storage_options={"fault_plan": {"seed": 0, "crash_after_op": ("write", crash_at)}},
)
print("UNEXPECTED_COMPLETION", flush=True)
"""


@pytest.mark.chaos
@needs_native
def test_sigkill_mid_compressed_take_salvage_reuses_blobs(tmp_path):
    """SIGKILL after N compressed blob writes → fsck torn; a retake with
    the same state re-compresses deterministically and the dual-hash
    rule licenses reuse of the intact COMPRESSED blobs; the final
    snapshot restores bit-exact and scrubs clean."""
    from tpusnap.lifecycle import fsck_snapshot

    path = str(tmp_path / "snap")
    proc = subprocess.run(
        [sys.executable, "-c", _COMPRESSED_CRASH_CHILD, path, "6"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        text=True,
        timeout=150,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert proc.returncode == -signal.SIGKILL, proc.stdout[-2000:]

    report = fsck_snapshot(path)
    assert report.state == "torn", report.summary()
    assert report.salvage_bytes_present > 0

    rng = np.random.default_rng(0)
    expected = {}
    for i in range(10):
        a = rng.standard_normal((256, 256)).astype(np.float32)
        expected[f"w{i}"] = (
            a.view(np.uint32) & np.uint32(0xFFFF0000)
        ).view(np.float32)

    before = telemetry.counter_value("salvage.bytes_salvaged")
    with override_compress(
        mode="on", min_blob_bytes=65536
    ), override_batching_disabled(True):
        Snapshot.take(path, {"app": StateDict(**expected)})
    salvaged = telemetry.counter_value("salvage.bytes_salvaged") - before
    assert salvaged >= 0.5 * report.salvage_bytes_present, (
        salvaged, report.salvage_bytes_present,
    )
    assert fsck_snapshot(path).state == "committed"
    assert verify_snapshot(path).clean
    raw = sum(v.nbytes for v in expected.values())
    assert _payload_bytes(path) < raw * 0.8  # the committed blobs ARE compressed
    tgt = {"app": StateDict(**{k: np.zeros_like(v) for k, v in expected.items()})}
    Snapshot(path).restore(tgt)
    for k, v in expected.items():
        assert np.array_equal(tgt["app"][k], v), k


# ------------------------------------------------------------- tiering


@pytest.mark.tiering
@needs_native
def test_tiering_drain_counts_compressed_bytes(tmp_path):
    """A tiered compressed take: the lag gauge counts COMPRESSED bytes
    (the upload backlog the wire actually sees), the drain uploads them
    with journal evidence over the stored bytes, and the remote tier
    restores bit-exact."""
    from tpusnap.tiering import (
        drain_snapshot,
        parse_tier_url,
        tier_state_of_dir,
    )

    local = tmp_path / "local"
    remote = tmp_path / "remote"
    local.mkdir()
    remote.mkdir()
    url = f"tier+local={local}+remote=fs://{remote}/snap"
    a = _bf16ish((2048, 256), seed=13)
    from tpusnap.knobs import override_tier_drain

    with override_compress(
        mode="on", min_blob_bytes=65536
    ), override_batching_disabled(True), override_tier_drain(False):
        Snapshot.take(url, {"app": StateDict(w=a.copy())})
    local_dir = parse_tier_url(url).local_dir
    stored = _payload_bytes(local_dir)
    assert stored < a.nbytes * 0.8  # landed compressed locally
    st = tier_state_of_dir(local_dir)
    assert st["durability"] == "local-committed"
    assert 0 < st["lag_bytes"] <= stored + 4096  # compressed backlog
    assert st["lag_bytes"] < a.nbytes  # NOT the raw size

    report = drain_snapshot(url)
    assert report.state == "durable"
    assert tier_state_of_dir(local_dir)["lag_bytes"] == 0
    tgt = {"app": StateDict(w=np.zeros_like(a))}
    Snapshot(str(remote / "snap")).restore(tgt)
    assert np.array_equal(tgt["app"]["w"], a)
    assert verify_snapshot(str(remote / "snap")).clean


# -------------------------------------------------------- observability


@needs_native
@pytest.mark.parametrize("mode", ["on", "auto"])
def test_decision_and_ratio_ride_summary_history_and_prom(
    tmp_path, monkeypatch, mode
):
    """The resolved policy decision + codec counters land in the take
    summary, flow into the history event (flat gateable scalars) and
    the Prometheus textfile export. An ``auto`` decision carries what
    its sample read; a forced one took none."""
    from tpusnap.history import event_from_summary
    from tpusnap.metrics_export import (
        PrometheusTextfileSink,
        parse_prometheus_textfile,
    )

    a = _bf16ish((2048, 256), seed=14)
    path = str(tmp_path / "snap")
    monkeypatch.setattr(compress_mod, "AUTO_MIN_TAKE_BYTES", 1 << 18)
    compress_mod.note_pipe_ceiling(_ceiling_label(tmp_path), 0.001)
    with override_compress(
        mode=mode, min_blob_bytes=65536
    ), override_batching_disabled(True):
        Snapshot.take(path, {"app": StateDict(w=a.copy())})
    summary = telemetry.LAST_TAKE_SUMMARY
    comp = summary.get("compress")
    assert comp and comp["decision"] == "compress"
    sampled = mode == "auto"
    assert comp["reason"] == ("codec_outruns_pipe" if sampled else "mode_forced")
    assert comp["sample_bytes"] == (a.nbytes if sampled else 0)
    assert (comp["sample_gbps"] > 0) == sampled
    assert (0.3 < comp["sample_ratio"] < 0.5) == sampled
    assert summary["stages"].get("compress.sample", {}).get("count", 0) == sampled
    counters = summary["counters"]
    assert counters["compress.bytes_in"] == a.nbytes
    assert 0 < counters["compress.bytes_out"] < a.nbytes
    assert summary["stages"]["compress"]["count"] == 1

    ev = event_from_summary("take", summary)
    assert ev["compress_decision"] == "compress"
    assert ev["compress_ratio"] > 1.2
    assert (ev.get("compress_codec_gbps", 0) > 0) == sampled
    assert ev.get("compress_sample_ratio") == (comp["sample_ratio"] if sampled else None)
    assert ev["compress_bytes_out"] == counters["compress.bytes_out"]

    sink = PrometheusTextfileSink(directory=str(tmp_path / "prom"))
    sink.on_take_summary(summary)
    from tpusnap.knobs import get_job_id

    prom_file = os.path.join(
        str(tmp_path / "prom"),
        f"tpusnap_{get_job_id()}_rank{summary['rank']}.prom",
    )
    families = parse_prometheus_textfile(open(prom_file).read())
    assert families["tpusnap_compress_bytes_in_total"]["samples"]
    assert families["tpusnap_compress_bytes_out_total"]["samples"]

    # The cross-rank rollup folds the codec counters.
    rollup = (Snapshot(path).metadata.extras or {}).get("telemetry", {})
    assert rollup.get("counters", {}).get("compress.bytes_in") == a.nbytes


def test_analyze_attributes_compress_as_own_resource():
    from tpusnap.analyze import ADVICE, WORK_PRIORITY, classify_span

    assert classify_span("compress") == "compress"
    assert classify_span("compress.sample") == "compress"
    assert "compress" in WORK_PRIORITY
    assert "TPUSNAP_COMPRESS" in ADVICE["compress"]
    # The write-bound advice recommends the policy flip the other way.
    assert "TPUSNAP_COMPRESS" in ADVICE["storage_write"]


@needs_native
def test_restore_under_disabled_native_decodes_compressed(tmp_path):
    """A compressed snapshot restores bit-exact with the native engine
    disabled (pure-Python LZ4 decode + unshuffle) — slow, but never a
    bricked checkpoint on a host without a toolchain."""
    a = _bf16ish((512, 64), seed=15)  # small: the Python decoder is slow
    path = str(tmp_path / "snap")
    with override_compress(
        mode="on", min_blob_bytes=65536
    ), override_batching_disabled(True):
        Snapshot.take(path, {"app": StateDict(w=a.copy())})
    assert Snapshot(path).metadata.manifest["0/app/w"].codec
    child = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["TPUSNAP_DISABLE_NATIVE"] = "1"
import numpy as np
from tpusnap import Snapshot, StateDict
path = sys.argv[1]
a = np.zeros((512, 64), dtype=np.float32)
tgt = {"app": StateDict(w=a)}
Snapshot(path).restore(tgt)
np.save(sys.argv[2], tgt["app"]["w"])
"""
    out_npy = str(tmp_path / "restored.npy")
    proc = subprocess.run(
        [sys.executable, "-c", child, path, out_npy],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        text=True,
        timeout=300,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert proc.returncode == 0, proc.stdout[-3000:]
    assert np.array_equal(np.load(out_npy), a)
