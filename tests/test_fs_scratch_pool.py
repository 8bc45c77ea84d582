"""A restore's reads land in scratch memory that the same restore has read
into before (PR 47): the fs plug-in's pool of scratch buffers lives for one
burst of reads, a buffer comes back when the last reference to what was
handed out dies, and a reader's wait for one is bounded by the plug-in's
own timings."""

import asyncio
import gc
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpusnap import Snapshot, StateDict, _native, telemetry
from tpusnap.io_types import ReadIO, WriteIO
from tpusnap.knobs import override_slab_size_threshold_bytes
from tpusnap.storage_plugins import fs as fs_module
from tpusnap.storage_plugins.fs import FSStoragePlugin, _ScratchPool

_MIB = 1 << 20
_LEAF = _MIB  # float32 elements: a 4 MiB blob, the smallest that takes a scratch buffer
_LIMIT = fs_module._SCRATCH_BUFFERS


def _counters():
    return (
        telemetry.counter_value("read.scratch_fresh_bytes"),
        telemetry.counter_value("read.scratch_reused_bytes"),
    )


def _since(before):
    return tuple(now - then for now, then in zip(_counters(), before))


@pytest.fixture
def slow_touch(monkeypatch):
    """The first touch of a fresh buffer is what a fresh read pays for, as on
    the machine with the chip: 50 ms a buffer beside a read of milliseconds."""
    real = _native.touch_pages
    seconds = [0.05]

    def touch(buf):
        time.sleep(seconds[0])
        real(buf)

    monkeypatch.setattr(_native, "touch_pages", touch)
    # A restore's consumer runs programs on the CPU backend beside five other
    # test processes: tests that need its buffers back inside a reader's
    # bounded wait make the touch, and so the wait, longer.
    return lambda s: seconds.__setitem__(0, s)


@pytest.fixture
def slow_storage(monkeypatch):
    """The storage is what a fresh read pays for: 100 ms a read, and a touch
    of a millisecond."""
    real = fs_module._read_range

    def read_range(*a):
        time.sleep(0.1)
        return real(*a)

    monkeypatch.setattr(fs_module, "_read_range", read_range)


def _blob(i: int, nbytes: int = 4 * _MIB) -> bytes:
    return bytes([i % 251 + 1]) * nbytes


def _write(root, count, nbytes=4 * _MIB):
    plugin = FSStoragePlugin(root=str(root))

    async def put():
        for i in range(count):
            await plugin.write(WriteIO(path=f"b{i}", buf=_blob(i, nbytes)))
        await plugin.close()

    asyncio.run(put())


def _take(path, n_leaves, dtype=np.float32):
    # Noise: the codec's policy leaves it as it is, a 4 MiB blob a leaf.
    rng = np.random.default_rng(n_leaves)
    arrs = {f"w{i:02d}": rng.standard_normal(_LEAF).astype(dtype) for i in range(n_leaves)}
    with override_slab_size_threshold_bytes(1024):
        Snapshot.take(path, {"m": StateDict(**arrs)})
    return arrs


def _bf16_targets(arrs):
    """Device targets that make the consumer cast: its ``device_put`` array,
    which aliases the scratch memory on the CPU backend, is dropped once the
    cast has run, as a transfer that is done drops the host memory on the
    chip. The consumer's first ``device_put`` and the cast's program are paid
    for here, not beside the first reads."""
    targets = {"m": StateDict(**{k: jnp.zeros(_LEAF, jnp.bfloat16) for k in arrs})}
    like = next(iter(targets["m"].values()))
    jax.device_put(np.zeros(_LEAF, np.float32), like.sharding).astype(like.dtype).block_until_ready()
    return targets


def _pool_of(snap) -> _ScratchPool:
    plugin = snap._resources()[1]
    while not isinstance(plugin, FSStoragePlugin):
        plugin = plugin.inner if hasattr(plugin, "inner") else plugin._inner
    return plugin._scratch


def _settled(pool: _ScratchPool, out: int = 0) -> bool:
    """Whether ``out`` buffers are out, once the threads that passed the last
    results on have let go of them (a reader thread drops a read's result
    when it next runs, the loop when its task next stands aside)."""
    deadline = time.monotonic() + 5
    while pool._out != out and time.monotonic() < deadline:
        gc.collect()
        time.sleep(0.005)
    return pool._out == out


def _is_empty(pool: _ScratchPool) -> bool:
    return _settled(pool) and pool.held_bytes() == 0 and pool._in_flight == 0


def test_a_burst_larger_than_the_limit_reuses_what_came_back(tmp_path, slow_touch):
    """Three times as many reads as buffers, each buffer dropped once its
    bytes are checked: the later reads land in memory read into before."""
    count = 3 * _LIMIT
    _write(tmp_path, count)
    plugin = FSStoragePlugin(root=str(tmp_path))
    before = _counters()

    async def one(i):
        read_io = ReadIO(path=f"b{i}", expected_nbytes=4 * _MIB)
        await plugin.read(read_io)
        assert bytes(read_io.buf.getbuffer()) == _blob(i)

    async def go():
        await asyncio.gather(*(one(i) for i in range(count)))

    asyncio.run(go())
    fresh, reused = _since(before)
    assert fresh + reused == count * 4 * _MIB
    assert reused > 0 and fresh < count * 4 * _MIB
    assert _is_empty(plugin._scratch)
    asyncio.run(plugin.close())


def test_a_restore_through_a_backend_that_copies_reuses_and_is_bit_exact(tmp_path, slow_touch):
    """A float32 snapshot restored into bfloat16 device targets
    (``_bf16_targets``): the restore reuses scratch memory, every bit is
    right, and the pool holds nothing afterwards. (A consumer that five other
    test processes keep off the cores for longer than a reader may wait
    returns its buffers too late to be reused: then the restore is made
    again, and has to be right every time.)"""
    path = str(tmp_path / "s")
    arrs = _take(path, 3 * _LIMIT)
    total = len(arrs) * 4 * _MIB
    slow_touch(0.25)
    for _ in range(4):
        targets = _bf16_targets(arrs)
        snap = Snapshot(path)
        snap.restore(targets)
        for k, want in arrs.items():
            np.testing.assert_array_equal(
                np.asarray(targets["m"][k]), want.astype(jnp.bfloat16)
            )
        counters = telemetry.LAST_RESTORE_SUMMARY["counters"]
        assert counters["read.scratch_fresh_bytes"] + counters["read.scratch_reused_bytes"] == total
        stages = telemetry.LAST_RESTORE_SUMMARY["stages"]
        assert stages["read.scratch_wait"]["count"] == stages["read.work"]["count"] == len(arrs)
        assert _is_empty(_pool_of(snap))
        if counters["read.scratch_reused_bytes"]:
            break
    assert counters["read.scratch_reused_bytes"] > 0
    assert counters["read.scratch_fresh_bytes"] < total


def test_every_buffer_held_for_good_each_read_gets_fresh_memory(tmp_path, slow_touch):
    """The CPU backend's ``device_put`` aliases the host memory for the
    restored array's life, so no buffer ever comes back: nothing is handed
    out twice, every read gets memory of its own after a bounded wait, only
    the readers' first waits run to their end, and every bit is right."""
    path = str(tmp_path / "s")
    arrs = _take(path, 3 * _LIMIT)
    targets = {"m": StateDict(**{k: jnp.zeros(_LEAF, jnp.float32) for k in arrs})}
    t0 = time.monotonic()
    Snapshot(path).restore(targets)
    took = time.monotonic() - t0
    for k, want in arrs.items():
        np.testing.assert_array_equal(np.asarray(targets["m"][k]), want)
    counters = telemetry.LAST_RESTORE_SUMMARY["counters"]
    assert counters["read.scratch_reused_bytes"] == 0
    assert counters["read.scratch_fresh_bytes"] == len(arrs) * 4 * _MIB
    stages = telemetry.LAST_RESTORE_SUMMARY["stages"]
    wait, work = stages["read.scratch_wait"], stages["read.work"]
    # A reader waits no longer than a fresh read took; one that came before
    # any fresh read had ended (eight threads, four buffers) waits for the
    # first to end and from then as long again.
    assert wait["max_s"] <= 2 * work["max_s"] + 0.02
    # One wait a reader thread, at the most, before the pool stops waiting
    # for what does not come back.
    assert wait["total_s"] <= 8 * (2 * work["max_s"] + 0.02)
    assert took < 30
    # The restored arrays still alias what they were given.
    for k, want in arrs.items():
        np.testing.assert_array_equal(np.asarray(targets["m"][k]), want)


def test_a_buffer_with_a_live_view_or_a_derived_array_is_never_handed_out(tmp_path, slow_touch):
    """A memoryview of what was handed out, and an array built over it,
    keep their bytes through a burst many times the pool's size; once
    they are gone the memory is used again."""
    count = 3 * _LIMIT
    _write(tmp_path, count)
    plugin = FSStoragePlugin(root=str(tmp_path))
    kept = {}

    async def one(i):
        read_io = ReadIO(path=f"b{i}", expected_nbytes=4 * _MIB)
        await plugin.read(read_io)
        if i < 4:
            view = read_io.buf.getbuffer()
            # an array over a slice of a view of the view
            kept[i] = (view, np.frombuffer(memoryview(view)[4096:], dtype=np.uint8))
        else:
            assert bytes(read_io.buf.getbuffer()) == _blob(i)

    async def go(indices):
        await asyncio.gather(*(one(i) for i in indices))

    pool = plugin._scratch
    with pool.reading():  # one burst for both rounds
        asyncio.run(go(range(count)))
        for i, (view, arr) in kept.items():
            assert bytes(view) == _blob(i)
            assert (arr == i % 251 + 1).all()
        addresses = {np.frombuffer(view, dtype=np.uint8).ctypes.data for view, _ in kept.values()}
        assert len(addresses) == 4
        assert _settled(pool, 4)
        # The views go, the derived arrays stay: still out.
        kept = {i: pair[1] for i, pair in kept.items()}
        del view, arr
        gc.collect()
        time.sleep(0.05)
        assert pool._out == 4
        before = _counters()
        asyncio.run(go(range(4, 4 + _LIMIT)))
        assert all((kept[i] == i % 251 + 1).all() for i in kept)
        kept.clear()
        assert _settled(pool) and pool.held_bytes() > 0
        asyncio.run(go(range(4, 8)))
        assert _since(before)[1] > 0
    assert _is_empty(pool)
    asyncio.run(plugin.close())


def _flip_a_bit(path):
    with open(path, "r+b") as f:
        f.seek(12345)
        byte = f.read(1)
        f.seek(12345)
        f.write(bytes([byte[0] ^ 0x10]))


def _cut_short(path):
    os.truncate(path, 3 * _MIB + 17)


@pytest.mark.parametrize("damage", [_flip_a_bit, _cut_short], ids=["checksum", "short_blob"])
def test_a_failed_read_strands_no_buffer(tmp_path, slow_touch, damage):
    """A restore that fails on one blob leaves the pool empty, and the next
    restore on the same handle starts with fresh memory and is bit-exact."""
    path = str(tmp_path / "s")
    arrs = _take(path, 2 * _LIMIT)
    blob = os.path.join(path, "0", "m", "w03")
    good = open(blob, "rb").read()
    damage(blob)
    snap = Snapshot(path)
    targets = _bf16_targets(arrs)
    with pytest.raises(Exception) as raised:
        snap.restore(targets)
    assert not isinstance(raised.value, AssertionError)
    del raised
    pool = _pool_of(snap)
    assert _is_empty(pool)
    with open(blob, "wb") as f:
        f.write(good)
    seen = _watch_takes(pool)
    snap.restore(targets)
    assert seen[0] == (0, 0)  # fresh memory, nothing had come back
    for k, want in arrs.items():
        np.testing.assert_array_equal(np.asarray(targets["m"][k]), want.astype(jnp.bfloat16))
    assert _is_empty(pool)


def test_a_cancelled_burst_strands_no_buffer(tmp_path, slow_touch):
    """Reads cancelled while their bodies run on the reader threads: once
    the threads are done the pool is empty, and the next burst starts so."""
    count = 3 * _LIMIT
    _write(tmp_path, count)
    plugin = FSStoragePlugin(root=str(tmp_path))
    pool = plugin._scratch

    async def go():
        reads = [ReadIO(path=f"b{i}", expected_nbytes=4 * _MIB) for i in range(count)]
        tasks = [asyncio.ensure_future(plugin.read(r)) for r in reads]
        await asyncio.sleep(0.03)  # the first bodies touch their buffers
        for t in tasks:
            t.cancel()
        done = await asyncio.gather(*tasks, return_exceptions=True)
        assert any(isinstance(d, asyncio.CancelledError) for d in done)
        await asyncio.get_running_loop().run_in_executor(None, plugin.drain_in_flight)

    asyncio.run(go())
    assert _is_empty(pool)
    seen = _watch_takes(pool)

    async def again():
        read_io = ReadIO(path="b0", expected_nbytes=4 * _MIB)
        await plugin.read(read_io)
        assert bytes(read_io.buf.getbuffer()) == _blob(0)

    asyncio.run(again())
    assert seen == [(0, 0)]
    assert _is_empty(pool)
    asyncio.run(plugin.close())


def _watch_takes(pool):
    """``(warm bytes of the buffer taken, buffers that had come back in the
    burst)`` of every ``take`` of ``pool`` from now on."""
    seen = []
    real = pool.take

    def take(size):
        entry = real(size)
        seen.append((min(entry.warm, size), pool._came_back))
        return entry

    pool.take = take
    return seen


def test_a_second_restore_on_one_handle_starts_with_an_empty_pool(tmp_path, slow_touch):
    """The plug-in is cached on the ``Snapshot`` handle; its pool is not a
    cache: the second restore reads into warm memory only once a buffer of
    its own has come back."""
    path = str(tmp_path / "s")
    arrs = _take(path, 3 * _LIMIT)
    snap = Snapshot(path)
    pool = _pool_of(snap)
    seen = _watch_takes(pool)
    slow_touch(0.25)
    reused = []
    for _ in range(2):
        del seen[:]
        targets = _bf16_targets(arrs)
        snap.restore(targets)
        assert _pool_of(snap) is pool
        assert len(seen) == len(arrs)
        assert seen[0] == (0, 0)
        assert all(came_back > 0 for warm, came_back in seen if warm)
        reused.append(telemetry.LAST_RESTORE_SUMMARY["counters"]["read.scratch_reused_bytes"])
        assert (reused[-1] > 0) == any(warm for warm, _ in seen)
        del targets
        assert _is_empty(pool)


def test_a_view_never_exposes_bytes_past_its_size(tmp_path):
    """A smaller blob, and a range of a blob, read into a longer buffer
    that an earlier read filled: the consumer's view ends where its bytes
    do, whatever it derives from it."""
    plugin = FSStoragePlugin(root=str(tmp_path))
    big, small = b"\xaa" * (8 * _MIB), b"\x55" * (5 * _MIB + 123)

    async def go():
        await plugin.write(WriteIO(path="big", buf=big))
        await plugin.write(WriteIO(path="small", buf=small))
        read_io = ReadIO(path="big", expected_nbytes=len(big))
        await plugin.read(read_io)
        assert bytes(read_io.buf.getbuffer()) == big
        del read_io
        # The loop and the reader thread drop a read's result when they next run.
        await asyncio.sleep(0.05)
        before = _counters()
        for asked, want in (
            ({"expected_nbytes": len(small)}, small),
            ({"byte_range": (100, 100 + 4 * _MIB)}, small[100 : 100 + 4 * _MIB]),
            # a length that is wrong: what is there is delivered, no more
            ({"expected_nbytes": 7 * _MIB}, small),
        ):
            read_io = ReadIO(path="small", **asked)
            await plugin.read(read_io)
            view = read_io.buf.getbuffer()
            assert view.nbytes == len(want) == len(read_io.buf)
            assert read_io.buf.getvalue() == want
            assert np.frombuffer(view, dtype=np.uint8).nbytes == len(want)
            assert b"\xaa" not in bytes(view)
            with pytest.raises((IndexError, ValueError)):
                view[len(want)]
            del view, read_io
            await asyncio.sleep(0.05)
        assert _since(before) == (0, 2 * len(small) + 4 * _MIB)  # all three into the long buffer

    with plugin._scratch.reading():
        asyncio.run(go())
    assert _is_empty(plugin._scratch)
    asyncio.run(plugin.close())


def test_where_the_storage_sets_the_pace_a_reader_never_waits(tmp_path, slow_storage):
    """The first touch is a small share of a fresh read's time: with every
    buffer out a reader allocates at once, and still takes a free buffer
    where there happens to be one."""
    count = 3 * _LIMIT
    _write(tmp_path, count)
    plugin = FSStoragePlugin(root=str(tmp_path))
    pool = plugin._scratch
    waits = []
    real = pool.take

    def take(size):
        t = time.monotonic()
        entry = real(size)
        waits.append(time.monotonic() - t)
        return entry

    pool.take = take
    kept = []

    async def one(i, keep):
        read_io = ReadIO(path=f"b{i}", expected_nbytes=4 * _MIB)
        await plugin.read(read_io)
        assert bytes(read_io.buf.getbuffer()) == _blob(i)
        if keep:
            kept.append(read_io.buf.getbuffer())

    async def go(indices, keep):
        await asyncio.gather(*(one(i, keep) for i in indices))

    with pool.reading():
        asyncio.run(go(range(count), keep=True))
        assert len(waits) == count and max(waits) < 0.05  # under one read of 100 ms
        assert pool._dry_at is None  # no wait ran out: none was begun
        assert _settled(pool, count)
        del kept[:]
        assert _settled(pool) and 0 < pool.held_bytes() <= _LIMIT * 4 * _MIB
        before = _counters()
        asyncio.run(go(range(4), keep=False))
        assert _since(before)[1] > 0
    assert _is_empty(pool)
    asyncio.run(plugin.close())


def test_the_pool_never_keeps_more_buffers_than_its_limit():
    """Reads of growing size cannot use what came back: the pool keeps no
    more free buffers than its limit, the largest, whatever comes back
    (after waits that ran out more than the limit are out)."""
    pool = _ScratchPool(limit=3)
    with pool.reading():
        views = []
        for k in range(1, 7):
            entry = pool.take(k * 4096)
            assert entry.buf.nbytes == k * 4096 and entry.warm == 0
            views.append(pool.view(entry, k * 4096))
            pool.landed(entry, k * 4096, 1e-3, 1e-6)
        assert pool._out == 6  # all out: over the limit, as after waits that ran out
        del views[:3]
        assert pool._out == 3 and pool.held_bytes() == (1 + 2 + 3) * 4096
        del views[:]
        assert pool._out == 0
        assert pool.held_bytes() == (4 + 5 + 6) * 4096  # the three largest
        entry = pool.take(7 * 4096)  # nothing fits: a fresh one
        assert entry.warm == 0 and entry.buf.nbytes == 7 * 4096
        assert pool.take(4096).buf.nbytes == 4 * 4096  # the smallest that fits
        assert pool.held_bytes() == (5 + 6) * 4096
    assert pool.held_bytes() == 0 and pool._out == 0


def test_a_wait_is_bounded_by_what_a_fresh_read_took_and_ends_at_a_return():
    """The pool alone: with the limit out, a reader waits for a return and
    no longer than fresh reads of that size have taken; a return ends the
    wait; after a wait that ran out none is begun until something comes back."""
    pool = _ScratchPool(limit=2)
    size = 1 << 16
    with pool.reading():
        views = []
        for _ in range(2):
            entry = pool.take(size)
            views.append(pool.view(entry, size))
            entry.warm = size
            pool.landed(entry, size, 0.06, 0.04)  # a fresh read took 0.1 s, most of it the touch
        threading.Timer(0.02, views.pop).start()  # a consumer lets one go
        t = time.monotonic()
        entry = pool.take(size)
        assert entry.warm == size and time.monotonic() - t < 0.09
        views.append(pool.view(entry, size))
        pool.landed(entry, size, 0.0, 0.001)
        assert pool._came_back == 1
        t = time.monotonic()
        entry = pool.take(size)  # nothing comes back: fresh, after the bound
        assert entry.warm == 0 and 0.09 < time.monotonic() - t < 0.5
        assert pool._dry_at == 1  # dry since the one that came back
    with pool.reading():
        views = []
        for _ in range(2):
            entry = pool.take(size)
            views.append(pool.view(entry, size))
            pool.landed(entry, size, 0.06, 0.04)
        t = time.monotonic()
        views.append(pool.view(pool.take(size), size))
        assert 0.09 < time.monotonic() - t < 0.5
        assert pool._dry_at == 0
        t = time.monotonic()
        views.append(pool.view(pool.take(size), size))
        assert time.monotonic() - t < 0.05  # nothing has come back: no second wait


def test_a_reader_that_comes_before_the_first_timing_waits_for_it_and_then_its_bound():
    """More reader threads than buffers: the readers that find the limit out
    before any fresh read has ended wait for the first to end (it says how
    long a wait may be) and from then no longer than it took."""
    pool = _ScratchPool(limit=1)
    size = 1 << 16
    with pool.reading():
        first = pool.take(size)
        view = pool.view(first, size)
        took = []

        def late_reader():
            t = time.monotonic()
            entry = pool.take(size)
            took.append((time.monotonic() - t, entry.warm))
            pool.landed(entry, size, 0.0, 0.001)

        reader = threading.Thread(target=late_reader)
        reader.start()
        time.sleep(0.1)
        assert not took  # the first read is still landing: no timing, no bound
        first.warm = size
        pool.landed(first, size, 0.12, 0.08)  # it took 0.2 s
        time.sleep(0.05)
        assert not took  # inside its bounded wait
        del view  # the consumer lets go
        reader.join(5)
        assert took and took[0][1] == size and 0.14 < took[0][0] < 0.3
