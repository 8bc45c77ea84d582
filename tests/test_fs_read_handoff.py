"""A restore's read reaches a reader thread without a blocking call on the
event loop's thread (PR 44): the blob's length comes with the request, and
the fs plug-in's reader threads exist before the first read's body runs."""

import asyncio
import importlib.util
import os
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from tpusnap import Snapshot, StateDict, telemetry
from tpusnap.io_types import ReadIO, StoragePlugin, WriteIO
from tpusnap.knobs import override_slab_size_threshold_bytes
from tpusnap.retry import RetryingStoragePlugin
from tpusnap.storage_plugins import fs as fs_module
from tpusnap.storage_plugins.fs import FSStoragePlugin

_LEAF = 1 << 20  # float32 elements: a 4 MiB blob, read on a reader thread


def _fs_threads():
    return {t for t in threading.enumerate() if t.name.startswith("tpusnap-fs")}


def _take_whole_blobs(path, n_leaves=4):
    arrs = {f"w{i}": np.arange(_LEAF, dtype=np.float32) + i for i in range(n_leaves)}
    with override_slab_size_threshold_bytes(1024):
        Snapshot.take(path, {"m": StateDict(**arrs)})
    return arrs


def test_restore_asks_no_size_on_the_loops_thread(tmp_path, monkeypatch):
    """Through the plug-in as a URL builds it (retry wrapper on): no
    ``os.stat`` / ``os.fstat`` / ``os.path.getsize`` of a blob on the
    thread that runs the restore's event loop; the size is checked on
    the reader threads; the counters say so."""
    path = str(tmp_path / "s")
    arrs = _take_whole_blobs(path)
    blobs = {os.path.join(path, "0", "m", k) for k in arrs}
    assert all(os.path.isfile(b) for b in blobs)
    asked = []  # (function, blob, thread)
    real_stat, real_fstat = os.stat, os.fstat

    def note(fn, what):
        if isinstance(what, int):
            try:
                what = os.readlink(f"/proc/self/fd/{what}")
            except OSError:
                return
        what = os.fspath(what)
        if what in blobs:
            asked.append((fn, what, threading.current_thread().name))

    def stat(p, *a, **k):
        note("stat", p)
        return real_stat(p, *a, **k)

    def fstat(fd):
        note("fstat", fd)
        return real_fstat(fd)

    monkeypatch.setattr(os, "stat", stat)  # os.path.getsize is os.stat(...).st_size
    monkeypatch.setattr(os, "fstat", fstat)

    snap = Snapshot(path)
    assert isinstance(snap._resources()[1], RetryingStoragePlugin)
    # Two device targets (the native path) and two host ones (in place).
    targets = {
        "m": StateDict(
            w0=jnp.zeros(_LEAF, jnp.float32),
            w1=jnp.zeros(_LEAF, jnp.float32),
            w2=np.zeros(_LEAF, np.float32),
            w3=np.zeros(_LEAF, np.float32),
        )
    }
    loop_thread = threading.current_thread().name
    snap.restore(targets)
    for k, want in arrs.items():
        np.testing.assert_array_equal(np.asarray(targets["m"][k]), want)

    assert asked, "the real size is checked where the blob is read"
    assert {b for _, b, _ in asked} == blobs
    on_loop = [a for a in asked if a[2] == loop_thread]
    assert not on_loop, on_loop
    assert all(t.startswith("tpusnap-fs") for _, _, t in asked), asked
    summary = telemetry.LAST_RESTORE_SUMMARY
    assert summary["counters"].get("read.length_known") == len(blobs)
    assert summary["counters"].get("read.length_asked", 0) == 0
    assert summary["gauges"].get("fs.readers_at_first_read") == 8.0


def test_a_read_without_a_length_asks_as_before(tmp_path):
    """``expected_nbytes`` None (inspect, lifecycle, tiering, cas, delta):
    the plug-in asks for the size and counts that it asked."""
    plugin = FSStoragePlugin(root=str(tmp_path))
    data = os.urandom(5 << 20)

    async def go():
        await plugin.write(WriteIO(path="b", buf=data))
        before = telemetry.counter_value("read.length_asked")
        read_io = ReadIO(path="b")
        await plugin.read(read_io)
        assert bytes(read_io.buf.getbuffer()) == data
        assert telemetry.counter_value("read.length_asked") == before + 1
        # ... and a length that is wrong in either direction reads what
        # is there, whole, on every path (native, in place, small).
        for wrong in (len(data) - 4096, len(data) + 4096, 100):
            read_io = ReadIO(path="b", expected_nbytes=wrong)
            await plugin.read(read_io)
            assert bytes(read_io.buf.getbuffer()) == data
            dst = np.zeros(wrong, np.uint8)
            read_io = ReadIO(path="b", expected_nbytes=wrong, into=memoryview(dst))
            await plugin.read(read_io)
            assert not read_io.in_place and not dst.any()
            assert bytes(read_io.buf.getbuffer()) == data
        await plugin.close()

    asyncio.run(go())


def test_reader_threads_exist_before_the_first_read(tmp_path, monkeypatch):
    """All eight ``tpusnap-fs`` threads are alive when the first reader
    body runs, no later submit starts one, a plug-in that only read the
    metadata started none, and ``close()`` leaves none."""
    root = str(tmp_path)
    blob = os.urandom(4 << 20)
    writer = FSStoragePlugin(root=root)
    others = _fs_threads()

    async def put():
        await writer.write(WriteIO(path=".snapshot_metadata", buf=b"{}"))
        for i in range(12):
            await writer.write(WriteIO(path=f"b{i}", buf=blob))
        await writer.close()

    asyncio.run(put())
    assert _fs_threads() <= others  # close() joined the writer's eight

    seen = []  # (alive tpusnap-fs threads of this plug-in, active_count) a body
    real_read_range = fs_module._read_range

    def read_range(*a):
        seen.append((len(_fs_threads() - others), threading.active_count()))
        return real_read_range(*a)

    monkeypatch.setattr(fs_module, "_read_range", read_range)
    plugin = FSStoragePlugin(root=root)
    counts = []
    real_submit = StoragePlugin._submit_tracked

    def submit(self, executor, fn):
        out = real_submit(self, executor, fn)
        counts.append(threading.active_count())
        return out

    monkeypatch.setattr(StoragePlugin, "_submit_tracked", submit)

    async def go():
        meta = ReadIO(path=".snapshot_metadata")
        await plugin.read(meta)
        assert bytes(meta.buf.getbuffer()) == b"{}"
        assert plugin._executor is None and _fs_threads() <= others
        reads = [ReadIO(path=f"b{i}", expected_nbytes=len(blob)) for i in range(12)]
        await asyncio.gather(*(plugin.read(r) for r in reads))
        assert all(bytes(r.buf.getbuffer()) == blob for r in reads)
        assert len(_fs_threads() - others) == 8
        await plugin.close()

    asyncio.run(go())
    assert len(seen) == 12 and len(counts) == 12
    assert seen[0][0] == 8, seen
    assert len(set(counts)) == 1, counts  # every submit after the first: a queue put
    assert _fs_threads() <= others


class _Recording(StoragePlugin):
    """Keeps the ReadIO its ``read`` is given."""

    def __init__(self, fail=None):
        self.seen = []
        self.fail = fail

    async def read(self, read_io):
        self.seen.append(read_io)
        if self.fail is not None:
            raise self.fail

    async def write(self, write_io):
        raise NotImplementedError

    async def delete(self, path):
        raise NotImplementedError


def _through_retry(read_io):
    inner = _Recording()
    asyncio.run(RetryingStoragePlugin(inner).read(read_io))
    return inner


def _through_faults(read_io):
    from tpusnap.faults import FaultInjectionStoragePlugin, FaultPlan, InjectedFaultError

    inner = _Recording()
    plugin = FaultInjectionStoragePlugin(
        inner, FaultPlan(transient_per_op=1, short_reads=True)
    )
    with pytest.raises(InjectedFaultError):
        asyncio.run(plugin.read(read_io))
    return inner


def _through_tiering(read_io):
    from tpusnap.tiering import TieredStoragePlugin

    plugin = object.__new__(TieredStoragePlugin)
    plugin.local = _Recording(fail=FileNotFoundError(read_io.path))
    plugin._remote = inner = _Recording()
    asyncio.run(plugin.read(read_io))
    return inner


def _through_cas(read_io):
    from tpusnap.cas import CASStore

    store = object.__new__(CASStore)
    store.plugin = inner = _Recording()
    asyncio.run(store.read_blob("0" * 16, read_io))
    return inner


@pytest.mark.parametrize(
    "through", [_through_retry, _through_faults, _through_tiering, _through_cas],
    ids=["retry", "faults", "tiering", "cas"],
)
def test_the_length_survives_a_middlewares_copy(through):
    """Each middleware that hands the inner plug-in a ReadIO of its own
    carries the length (dropped, the fs plug-in would silently ask again)."""
    asked = ReadIO(path="0/m/w", expected_nbytes=12345, want_crc=True)
    inner = through(asked)
    assert len(inner.seen) == 1 and inner.seen[0] is not asked
    assert inner.seen[0].expected_nbytes == 12345
    assert inner.seen[0].byte_range is None


def test_cold_read_probe_on_a_tiny_snapshot(tmp_path, capsys):
    """``scripts/cold_read_probe.py`` on the CPU host: blobs written,
    evicted and read back at each width; both calls timed alone and
    beside reads."""
    import json

    path = os.path.join(os.path.dirname(__file__), "..", "scripts", "cold_read_probe.py")
    spec = importlib.util.spec_from_file_location("cold_read_probe", path)
    probe = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(probe)
    rc = probe.main(
        ["--dir", str(tmp_path), "--blobs", "3", "--blob-mib", "1", "--min-mib", "0.5",
         "--widths", "1,2", "--calls", "3", "--beside", "2"]
    )
    assert rc == 0
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert lines[0]["probe"] == "blobs" and lines[0]["count"] == 3
    reads = [ln for ln in lines if ln["probe"] == "read"]
    assert [r["width"] for r in reads] == [1, 2]
    assert all(r["bytes"] == 3 << 20 and r["gb_per_s"] > 0 for r in reads)
    calls = [(ln["where"], ln["call"]) for ln in lines if ln["probe"] == "calls"]
    assert calls == [("alone", "getsize"), ("alone", "thread_start"),
                     ("beside_reads", "getsize"), ("beside_reads", "thread_start")]
    assert os.listdir(tmp_path) == []  # it removes what it wrote
    assert probe.main(["--path", str(tmp_path)]) == 2  # nothing to read there


@pytest.mark.parametrize("hold_ms", ["0", "2"])
def test_cold_read_probe_passes_buffers_between_reads(tmp_path, capsys, hold_ms):
    """``--reuse 2,6``: the threads share at most that many buffers, none
    there at the start; with six buffers for six blobs every read touches
    its own, with two the other four blobs land in memory read into before,
    whether a buffer comes back at once or a consumer keeps it a while."""
    import json

    path = os.path.join(os.path.dirname(__file__), "..", "scripts", "cold_read_probe.py")
    spec = importlib.util.spec_from_file_location("cold_read_probe", path)
    probe = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(probe)
    rc = probe.main(
        ["--dir", str(tmp_path), "--blobs", "6", "--blob-mib", "1", "--min-mib", "0.5",
         "--widths", "1,4", "--reuse", "2,6", "--hold-ms", hold_ms]
    )
    assert rc == 0
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    pools = [ln for ln in lines if ln["probe"] == "pool"]
    assert [(p["width"], p["buffers"]) for p in pools] == [(1, 2), (1, 6), (4, 2), (4, 6)]
    for p in pools:
        assert p["bytes"] == p["fresh_bytes"] + p["reused_bytes"] == 6 << 20
        assert p["buffers_made"] <= p["buffers"] and p["gb_per_s"] > 0
        assert p["fresh_bytes"] == p["buffers_made"] << 20
        assert p["touch_s"] > 0 and p["fresh_read_s"] > 0 and p["wait_s"] >= 0
        assert (p["reused_read_s"] > 0) == (p["reused_bytes"] > 0)
    assert all(p["reused_bytes"] >= 4 << 20 for p in pools if p["buffers"] == 2)
    assert not any(ln["probe"] == "read" for ln in lines)
    assert os.listdir(tmp_path) == []


def test_a_whole_blob_into_scratch_is_hashed_where_it_is_read(tmp_path):
    """A device target's blob is hashed on the reader thread (``want_crc``)
    and the consumer compares the value: no ``decode`` pass over the
    buffer on a consume thread, and a flipped bit still fails the restore."""
    from tpusnap._native import ChecksumError, available

    path = str(tmp_path / "s")
    arrs = _take_whole_blobs(path, n_leaves=2)
    targets = {"m": StateDict(**{k: jnp.zeros(_LEAF, jnp.float32) for k in arrs})}
    Snapshot(path).restore(targets)
    for k, want in arrs.items():
        np.testing.assert_array_equal(np.asarray(targets["m"][k]), want)
    stages = telemetry.LAST_RESTORE_SUMMARY["stages"]
    assert "read.work" in stages
    if available():
        assert "decode" not in stages, stages.get("decode")
    with open(os.path.join(path, "0", "m", "w1"), "r+b") as f:
        f.seek(12345)
        byte = f.read(1)
        f.seek(12345)
        f.write(bytes([byte[0] ^ 0x10]))
    with pytest.raises(ChecksumError):
        Snapshot(path).restore(targets)


@pytest.mark.parametrize("nbytes", [0, 1, 4095, 4096, 3 * 4096 + 17])
@pytest.mark.parametrize("native", [True, False], ids=["native", "no_native"])
def test_touch_pages_writes_inside_the_buffer_only(tmp_path, monkeypatch, nbytes, native):
    """``_native.touch_pages`` at every length, with and without the native
    library; a read lands in the buffer afterwards."""
    from tpusnap import _native

    if not native:
        monkeypatch.setattr(_native, "_load", lambda: None)
    guard = np.full(nbytes + 2 * 4096, 7, np.uint8)
    buf = guard[4096 : 4096 + nbytes]
    _native.touch_pages(buf)
    assert (guard[:4096] == 7).all() and (guard[4096 + nbytes :] == 7).all()
    data = os.urandom(nbytes)
    (tmp_path / "b").write_bytes(data)
    assert _native.read_range(str(tmp_path / "b"), 0, nbytes, buf) == nbytes
    assert buf.tobytes() == data
