"""fs plugin + registry + native helper tests (reference exercises its fs
plugin implicitly via Snapshot tests and tmp_path)."""

import asyncio
import os

import numpy as np
import pytest

from tpusnap import Snapshot, StateDict
from tpusnap.io_types import ReadIO, WriteIO
from tpusnap.knobs import override_slab_size_threshold_bytes
from tpusnap.storage_plugin import url_to_storage_plugin
from tpusnap.storage_plugins.fs import FSStoragePlugin


def _run(coro):
    return asyncio.run(coro)


def test_registry_schemes(tmp_path):
    from tpusnap.retry import RetryingStoragePlugin
    from tpusnap.storage_plugin import InstrumentedStoragePlugin

    # Built-in plugins come wrapped retry(instrument(raw)): whole-op
    # retry outermost, the histogram instrumentation inside it (so each
    # attempt is one latency sample, without backoff sleeps).
    p = url_to_storage_plugin(str(tmp_path))
    assert isinstance(p, RetryingStoragePlugin)
    assert isinstance(p.inner, InstrumentedStoragePlugin)
    assert isinstance(p.inner.inner, FSStoragePlugin)
    assert p.inner.label == "FSStoragePlugin"
    p = url_to_storage_plugin(f"fs://{tmp_path}")
    assert isinstance(p.inner.inner, FSStoragePlugin)
    # storage_options={"retry": False} drops retry, keeps instrumentation.
    p = url_to_storage_plugin(str(tmp_path), {"retry": False})
    assert isinstance(p, InstrumentedStoragePlugin)
    assert isinstance(p.inner, FSStoragePlugin)
    p = url_to_storage_plugin(f"fsspec+memory://snap")
    from tpusnap.storage_plugins.fsspec import FsspecStoragePlugin

    assert isinstance(p.inner.inner, FsspecStoragePlugin)
    with pytest.raises(RuntimeError, match="Unsupported storage scheme"):
        url_to_storage_plugin("bogus://x")
    # S3 construction succeeds without aiobotocore (deferred import so a
    # stub client can be injected); first real use raises. Unknown
    # attributes pass through the instrumentation wrapper.
    s3 = url_to_storage_plugin("s3://bucket/prefix")
    with pytest.raises(RuntimeError, match="aiobotocore"):
        _run(s3.inner._get_client())


def test_registry_chaos_scheme(tmp_path):
    """chaos+<scheme>:// composes Retrying(Instrumented(FaultInjection(
    raw))) so injected faults exercise the production retry path AND
    injected latency lands in the histograms as the fat tail it is."""
    from tpusnap.faults import FaultInjectionStoragePlugin, FaultPlan
    from tpusnap.retry import RetryingStoragePlugin
    from tpusnap.storage_plugin import InstrumentedStoragePlugin

    def _unwrap(plugin):
        assert isinstance(plugin, RetryingStoragePlugin)
        assert isinstance(plugin.inner, InstrumentedStoragePlugin)
        return plugin.inner.inner

    p = url_to_storage_plugin(f"chaos+fs://{tmp_path}")
    fault = _unwrap(p)
    assert isinstance(fault, FaultInjectionStoragePlugin)
    assert isinstance(fault.inner, FSStoragePlugin)
    # ...and the instrumentation labels by the RAW backend class.
    assert p.inner.label == "FSStoragePlugin"
    # default plan: ≥1 transient error per distinct op. (Attribute
    # passthrough: p.inner.plan delegates through the instrumentation.)
    assert fault.plan.transient_per_op == 1
    assert p.inner.plan.transient_per_op == 1
    # explicit plans ride storage_options (FaultPlan, spec str, or dict)
    p = url_to_storage_plugin(
        f"chaos+fs://{tmp_path}",
        {"fault_plan": FaultPlan(seed=7, transient_every=3, torn_writes=True)},
    )
    assert _unwrap(p).plan.seed == 7 and _unwrap(p).plan.torn_writes
    p = url_to_storage_plugin(
        f"chaos+fs://{tmp_path}",
        {"fault_plan": "seed=2,transient_per_op=2,latency_ms=1"},
    )
    assert _unwrap(p).plan.seed == 2
    assert _unwrap(p).plan.transient_per_op == 2
    assert abs(_unwrap(p).plan.latency_sec - 0.001) < 1e-9
    # chaos over the generic fsspec bridge
    p = url_to_storage_plugin("chaos+fsspec+memory://snapchaos")
    from tpusnap.storage_plugins.fsspec import FsspecStoragePlugin

    assert isinstance(_unwrap(p).inner, FsspecStoragePlugin)


def test_fs_write_read_roundtrip(tmp_path):
    plugin = FSStoragePlugin(root=str(tmp_path))

    async def go():
        data = os.urandom(1 << 16)
        await plugin.write(WriteIO(path="a/b/c", buf=memoryview(data)))
        read_io = ReadIO(path="a/b/c")
        await plugin.read(read_io)
        assert read_io.buf.getvalue() == data
        # ranged read
        read_io = ReadIO(path="a/b/c", byte_range=(100, 356))
        await plugin.read(read_io)
        assert read_io.buf.getvalue() == data[100:356]
        await plugin.delete("a/b/c")
        assert not (tmp_path / "a" / "b" / "c").exists()
        await plugin.close()

    _run(go())


def test_fs_large_write_native_path(tmp_path):
    plugin = FSStoragePlugin(root=str(tmp_path))
    data = os.urandom(5 * 1024 * 1024)  # over the native threshold

    async def go():
        await plugin.write(WriteIO(path="big", buf=memoryview(data)))
        read_io = ReadIO(path="big")
        await plugin.read(read_io)
        assert read_io.buf.getvalue() == data
        await plugin.close()

    _run(go())


def test_fs_direct_io_roundtrip(tmp_path):
    """O_DIRECT writes must be bit-exact for unaligned sizes (the aligned
    bulk goes through the direct fd, the tail through a buffered one) and
    the knob must force the buffered path."""
    from tpusnap import _native
    from tpusnap.knobs import override_direct_io_disabled

    for nbytes in (4 * 1024 * 1024, 8 * 1024 * 1024 + 4096, 9 * 1024 * 1024 + 7):
        data = os.urandom(nbytes)
        for disabled in (False, True):
            with override_direct_io_disabled(disabled):
                path = str(tmp_path / f"d{nbytes}_{disabled}")
                _native.write_file(path, memoryview(data))
                with open(path, "rb") as f:
                    assert f.read() == data
                # ranged reads: aligned, misaligned head/tail, past-EOF
                for off, n in ((0, nbytes), (4096, 5 * 1024 * 1024),
                               (1234, 4 * 1024 * 1024 + 77),
                               (nbytes - 100, 500),
                               # large request starting in the final
                               # partial block: empty aligned window
                               (nbytes - 3, 4 * 1024 * 1024)):
                    out = bytearray(n)
                    got = _native.read_range(path, off, n, out)
                    assert bytes(out[:got]) == data[off:off + n]


def test_fs_concurrent_writes(tmp_path):
    plugin = FSStoragePlugin(root=str(tmp_path))

    async def go():
        blobs = {f"obj{i}": os.urandom(10_000) for i in range(32)}
        await asyncio.gather(
            *(plugin.write(WriteIO(path=k, buf=v)) for k, v in blobs.items())
        )
        for k, v in blobs.items():
            read_io = ReadIO(path=k)
            await plugin.read(read_io)
            assert read_io.buf.getvalue() == v
        await plugin.close()

    _run(go())


def test_fsspec_memory_roundtrip():
    plugin = url_to_storage_plugin("fsspec+memory://snaptest")

    async def go():
        await plugin.write(WriteIO(path="x/y", buf=b"hello"))
        read_io = ReadIO(path="x/y")
        await plugin.read(read_io)
        assert read_io.buf.getvalue() == b"hello"
        read_io = ReadIO(path="x/y", byte_range=(1, 4))
        await plugin.read(read_io)
        assert read_io.buf.getvalue() == b"ell"
        await plugin.delete("x/y")
        await plugin.close()

    _run(go())


def test_sync_shims(tmp_path):
    plugin = FSStoragePlugin(root=str(tmp_path))
    plugin.sync_write(WriteIO(path="s", buf=b"sync"))
    read_io = ReadIO(path="s")
    plugin.sync_read(read_io)
    assert read_io.buf.getvalue() == b"sync"
    plugin.sync_close()


class TestNative:
    def test_write_and_read_range(self, tmp_path):
        from tpusnap import _native

        data = os.urandom(1 << 20)
        path = str(tmp_path / "n.bin")
        _native.write_file(path, memoryview(data))
        assert open(path, "rb").read() == data
        out = bytearray(1000)
        got = _native.read_range(path, 500, 1000, out)
        assert got == 1000 and bytes(out) == data[500:1500]
        # EOF-short read
        out = bytearray(100)
        got = _native.read_range(path, len(data) - 10, 100, out)
        assert got == 10 and bytes(out[:10]) == data[-10:]

    def test_memcpy(self):
        from tpusnap import _native

        src = os.urandom(3 << 20)
        dst = bytearray(len(src))
        _native.memcpy(dst, src)
        assert bytes(dst) == src
        with pytest.raises(ValueError):
            _native.memcpy(bytearray(5), b"123")

    def test_crc32c_known_vector(self):
        from tpusnap import _native

        if not _native.available():
            pytest.skip("native unavailable")
        # RFC 3720 test vector: crc32c of 32 zero bytes == 0x8a9136aa
        assert _native.crc32c(bytes(32)) == 0x8A9136AA
        assert _native.checksum_algorithm() == "crc32c"

    def test_disabled_fallback(self, tmp_path, monkeypatch):
        monkeypatch.setenv("TPUSNAP_DISABLE_NATIVE", "1")
        # force a fresh load decision in a subprocess to honor the env var
        import subprocess
        import sys

        code = (
            "import os; os.environ['TPUSNAP_DISABLE_NATIVE']='1';"
            "from tpusnap import _native;"
            f"p=r'{tmp_path}/f.bin';"
            "_native.write_file(p, b'abc');"
            "assert open(p,'rb').read()==b'abc';"
            "assert not _native.available();"
            "print('fallback-ok')"
        )
        out = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True, cwd="/root/repo"
        )
        assert "fallback-ok" in out.stdout, out.stderr


def test_register_storage_plugin_runtime(tmp_path):
    """Runtime-registered schemes take effect without packaging
    (complements the entry-point group)."""
    from tpusnap.storage_plugin import (
        register_storage_plugin,
        unregister_storage_plugin,
        url_to_storage_plugin,
    )
    from tpusnap.storage_plugins.fs import FSStoragePlugin

    calls = {}

    def factory(path, storage_options):
        calls["path"] = path
        return FSStoragePlugin(root=str(tmp_path / path), storage_options=storage_options)

    register_storage_plugin("memtest", factory)
    try:
        plugin = url_to_storage_plugin("memtest://sub/dir")
        assert isinstance(plugin, FSStoragePlugin)
        assert calls["path"] == "sub/dir"
    finally:
        unregister_storage_plugin("memtest")
    with pytest.raises(RuntimeError):
        url_to_storage_plugin("memtest://sub/dir")


class TestReadInto:
    """In-place reads: bytes land directly in the consumer-provided
    destination with the checksum fused into the native copy-out."""

    def test_read_range_into_correctness(self, tmp_path):
        from tpusnap import _native

        rng = np.random.default_rng(3)
        n = 9 * 1024 * 1024 + 1234
        data = rng.integers(0, 255, n, dtype=np.uint8).tobytes()
        path = str(tmp_path / "blob")
        open(path, "wb").write(data)
        cases = [
            (0, n),                       # whole file
            (0, 5 * 1024 * 1024 + 17),    # aligned start, odd length
            (1, n - 1),                   # misaligned head
            (4096, 6 * 1024 * 1024),      # aligned window
            (777, 8 * 1024 * 1024 + 5),   # misaligned head + tail
            (n - 100, 100),               # small tail
            (n - 100, 500),               # EOF-short
            (0, 1000),                    # small (buffered path)
        ]
        for off, ln in cases:
            out = np.empty(ln, dtype=np.uint8)
            got, crc, algo = _native.read_range_into(
                path, off, ln, out, want_crc=True
            )
            expect = data[off : off + ln]
            assert got == len(expect), (off, ln)
            assert out[:got].tobytes() == expect, (off, ln)
            assert crc == _native.crc32c(expect), (off, ln)
        # aligned destination takes the zero-copy direct path
        out = _native.aligned_empty(8 * 1024 * 1024)
        got, crc, algo = _native.read_range_into(
            path, 0, 8 * 1024 * 1024, out, want_crc=True
        )
        assert got == 8 * 1024 * 1024
        assert bytes(out) == data[:got] and crc == _native.crc32c(data[:got])
        # want_crc=False reports no checksum
        got, crc, algo = _native.read_range_into(
            path, 0, 4 * 1024 * 1024, np.empty(4 * 1024 * 1024, np.uint8)
        )
        assert got == 4 * 1024 * 1024 and crc is None

    def test_fs_plugin_honors_into(self, tmp_path):
        plugin = FSStoragePlugin(root=str(tmp_path))
        data = os.urandom(5 * 1024 * 1024)

        async def go():
            await plugin.write(WriteIO(path="b", buf=data))
            dst = np.empty(len(data), dtype=np.uint8)
            read_io = ReadIO(path="b", into=memoryview(dst), want_crc=True)
            await plugin.read(read_io)
            assert read_io.in_place
            assert dst.tobytes() == data
            from tpusnap import _native

            if _native.available():
                assert read_io.crc32c == _native.crc32c(data)
                assert read_io.crc_algo == "crc32c"
            # the generic buf view still works for fallback consumers
            assert bytes(read_io.buf.getbuffer()) == data
            await plugin.close()

        _run(go())

    def test_restore_lands_in_place(self, tmp_path):
        """A numpy restore target with matching dtype/shape receives the
        bytes directly — the future resolves to the SAME array object."""
        arr = np.random.default_rng(5).standard_normal(500_000).astype(np.float32)
        Snapshot.take(str(tmp_path / "s"), {"m": StateDict(w=arr.copy())})
        target_arr = np.zeros_like(arr)
        target = {"m": StateDict(w=target_arr)}
        Snapshot(str(tmp_path / "s")).restore(target)
        assert target["m"]["w"] is target_arr
        assert np.array_equal(target_arr, arr)

    def test_in_place_short_read_fails_loudly(self, tmp_path):
        """A truncated blob must raise, not silently leave a partial
        restore in the target — even with checksum verification off
        (the truncated size disqualifies the in-place path, and the
        generic deserialize raises on the size mismatch)."""
        from tpusnap.knobs import override_checksum_disabled

        arr = np.arange(300_000, dtype=np.float32)
        with override_slab_size_threshold_bytes(1024):
            Snapshot.take(str(tmp_path / "s"), {"m": StateDict(w=arr)})
        blob = str(tmp_path / "s" / "0" / "m" / "w")
        assert os.path.isfile(blob)
        with open(blob, "r+b") as f:
            f.truncate(arr.nbytes // 2)
        for checksum_off in (False, True):
            with override_checksum_disabled(checksum_off):
                target = {"m": StateDict(w=np.zeros_like(arr))}
                with pytest.raises((IOError, ValueError)):
                    Snapshot(str(tmp_path / "s")).restore(target)

    @pytest.mark.parametrize("checksum_off", [False, True], ids=["crc_on", "crc_off"])
    @pytest.mark.parametrize("target", ["numpy", "jax"])
    @pytest.mark.parametrize("damage", ["truncated", "longer"])
    def test_blob_of_another_length_fails_loudly(
        self, tmp_path, damage, target, checksum_off
    ):
        """A blob shorter OR longer than its manifest entry implies fails
        the restore, for an in-place (numpy) and a device (jax) target,
        with checksums on and off: the length a read takes from its
        request picks the path, never what counts as the blob. The errors
        are the ones the parent raised (recorded there, PR 44): every
        case a ChecksumError with checksums on (an IOError) and, off, the
        deserialize's ValueError. The blob is 4 MiB, so the jax target's
        read takes the plug-in's reader threads, as a large leaf's does."""
        import jax.numpy as jnp

        from tpusnap._native import ChecksumError
        from tpusnap.knobs import override_checksum_disabled

        arr = np.arange(1 << 20, dtype=np.float32)
        with override_slab_size_threshold_bytes(1024):
            Snapshot.take(str(tmp_path / "s"), {"m": StateDict(w=arr)})
        blob = str(tmp_path / "s" / "0" / "m" / "w")
        assert os.path.getsize(blob) == arr.nbytes
        with open(blob, "r+b") as f:
            if damage == "truncated":
                f.truncate(arr.nbytes // 2)
            else:
                f.seek(0, os.SEEK_END)
                f.write(b"\x01" * 4096)
        before = np.full_like(arr, -1.0)
        dest = before.copy() if target == "numpy" else jnp.asarray(before)
        state = {"m": StateDict(w=dest)}
        with override_checksum_disabled(checksum_off):
            with pytest.raises(ValueError if checksum_off else ChecksumError):
                Snapshot(str(tmp_path / "s")).restore(state)
        # The error is the whole outcome: the target is not quietly
        # replaced by a prefix of the blob.
        if target == "jax":
            assert state["m"]["w"] is dest
        np.testing.assert_array_equal(np.asarray(dest)[arr.size // 2 :], -1.0)


class TestAbortPath:
    """A failed read must surface the ORIGINAL error, leave no stranded
    tasks on the (cached, reused) event loop, and leave no plugin
    thread still writing into caller-owned memory."""

    def test_failed_restore_surfaces_original_error_and_loop_reusable(
        self, tmp_path
    ):
        from tpusnap._native import ChecksumError

        arrs = {
            f"w{i}": np.arange(400_000, dtype=np.float32) + i for i in range(6)
        }
        Snapshot.take(str(tmp_path / "s"), {"m": StateDict(**arrs)})
        snap = Snapshot(str(tmp_path / "s"))
        entry = snap.get_manifest()["0/m/w2"]
        blob = str(tmp_path / "s" / "0" / "m" / "w2")
        if not os.path.isfile(blob):
            import glob as _glob

            blob = _glob.glob(str(tmp_path / "s" / "batched" / "*"))[0]
        off = (entry.byte_range[0] if entry.byte_range else 0) + 16
        with open(blob, "r+b") as fh:
            fh.seek(off)
            b = fh.read(1)
            fh.seek(off)
            fh.write(bytes([b[0] ^ 0xFF]))

        # Repeated fail -> reuse cycles on the same handle: the original
        # ChecksumError (not a secondary abort artifact) must surface
        # every time, and clean blobs must read correctly afterwards.
        for _ in range(3):
            with pytest.raises(ChecksumError, match="w2"):
                snap.restore(
                    {
                        "m": StateDict(
                            **{k: np.zeros_like(v) for k, v in arrs.items()}
                        )
                    }
                )
            out = snap.read_object("0/m/w5")
            np.testing.assert_array_equal(out, arrs["w5"])
        # After the abort drain, the plugin reports no in-flight work.
        _, storage = snap._resources()
        storage.drain_in_flight()
        assert not storage.__dict__.get("_tracked_inflight")
        snap.close()

    def test_run_on_loop_drains_stranded_task(self):
        """A BaseException escaping run_until_complete must not leave
        the top-level task pending on the loop."""
        import asyncio

        from tpusnap.io_types import run_on_loop

        loop = asyncio.new_event_loop()
        state = {"cancelled": False}

        async def work():
            try:
                await asyncio.sleep(60)
            except asyncio.CancelledError:
                state["cancelled"] = True
                raise

        task = loop.create_task(work())

        # Simulate an interrupt escaping the loop machinery: stop the
        # loop via a KeyboardInterrupt raised from a scheduled callback.
        def boom():
            raise KeyboardInterrupt

        loop.call_later(0.05, boom)
        with pytest.raises(KeyboardInterrupt):
            run_on_loop(loop, task)
        assert task.done() and state["cancelled"]
        # The loop is clean: a fresh coroutine runs unobstructed.
        assert loop.run_until_complete(asyncio.sleep(0, result=42)) == 42
        loop.close()


def test_write_atomic_durable_flag(tmp_path):
    """durable=True fsyncs (file + parent dir) and still lands the same
    bytes; the take commit honors TPUSNAP_DURABLE_COMMIT."""
    import asyncio
    import os

    from tpusnap.io_types import WriteIO
    from tpusnap.storage_plugins.fs import FSStoragePlugin

    loop = asyncio.new_event_loop()
    plugin = FSStoragePlugin(str(tmp_path))
    fsyncs = []
    real_fsync = os.fsync
    try:
        plugin.sync_write_atomic(
            WriteIO(path="meta", buf=b"payload-1"), loop, durable=False
        )
        assert (tmp_path / "meta").read_bytes() == b"payload-1"
        import unittest.mock as mock

        with mock.patch("os.fsync", side_effect=lambda fd: (fsyncs.append(fd), real_fsync(fd))):
            plugin.sync_write_atomic(
                WriteIO(path="meta", buf=b"payload-2"), loop, durable=True
            )
        assert (tmp_path / "meta").read_bytes() == b"payload-2"
        assert len(fsyncs) == 2  # temp file + parent directory
    finally:
        plugin.sync_close(loop)
        loop.close()


def test_durable_commit_knob_round_trip(tmp_path, monkeypatch):
    import numpy as np

    from tpusnap import Snapshot, StateDict

    monkeypatch.setenv("TPUSNAP_DURABLE_COMMIT", "1")
    path = str(tmp_path / "snap")
    Snapshot.take(path, {"app": StateDict(w=np.arange(32, dtype=np.float32))})
    target = {"app": StateDict(w=np.zeros(32, np.float32))}
    Snapshot(path).restore(target)
    assert np.array_equal(target["app"]["w"], np.arange(32, dtype=np.float32))
