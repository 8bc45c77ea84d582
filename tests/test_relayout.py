"""A host array in another order than C is turned into C order by staging
itself (``serialization.c_order_copy_into`` into a buffer of the staging
pool, ``io_preparers/array.py``), not by ``np.ascontiguousarray`` inside
``array_as_memoryview``: the copy alone against ``np.ascontiguousarray``,
then through ``Snapshot.take`` / ``async_take`` with what the take's
telemetry says of it."""

import threading

import ml_dtypes
import numpy as np
import pytest

import tpusnap._staging_pool as pool
from tpusnap import Snapshot, StateDict, metrics_sink, telemetry, verify_snapshot
from tpusnap.io_preparers import array as array_preparer
from tpusnap.serialization import RELAYOUT_MIN_BYTES, c_order_copy_into


def _values(shape, dtype, seed=0):
    rng = np.random.default_rng(seed)
    n = int(np.prod(shape))
    raw = rng.integers(0, 256, n * np.dtype(dtype).itemsize, dtype=np.uint8)
    return raw.view(dtype).reshape(shape)


def _fortran_2d(dtype=np.float32):
    return np.asfortranarray(_values((700, 900), dtype))


# A float32 [rows, 1024] of this many rows is 4 KiB under the constant.
_ROWS_UNDER = RELAYOUT_MIN_BYTES // 4096 - 1

_CASES = {
    "fortran_2d": _fortran_2d,
    "permuted_3d": lambda: _values((40, 50, 60), np.float32).transpose(2, 0, 1),
    "step_slice": lambda: _values((600, 800), np.float32)[::3, 1::2],
    "reversed": lambda: _values((600, 800), np.float32)[::-1],
    "broadcast": lambda: np.broadcast_to(_values((1, 900), np.float32), (700, 900)),
    "itemsize_1": lambda: _fortran_2d(np.uint8),
    "itemsize_2": lambda: _fortran_2d(np.int16),
    "itemsize_4": lambda: _fortran_2d(np.int32),
    "itemsize_8": lambda: _fortran_2d(np.float64),
    "bfloat16": lambda: _fortran_2d(ml_dtypes.bfloat16),
    "float8": lambda: _fortran_2d(ml_dtypes.float8_e4m3fn),
    "zero_size": lambda: np.asfortranarray(np.zeros((0, 7), np.float32)),
    "one_long_axis": lambda: _values((3, 1, 500_000), np.float32).transpose(2, 1, 0),
    "under_the_constant": lambda: np.asfortranarray(_values((_ROWS_UNDER, 1024), np.float32)),
    "over_the_constant": lambda: np.asfortranarray(_values((_ROWS_UNDER + 2, 1024), np.float32)),
}


@pytest.mark.parametrize("threads", [1, 4])
@pytest.mark.parametrize("case", sorted(_CASES))
def test_the_copy_is_np_ascontiguousarray_bit_for_bit(case, threads):
    src = _CASES[case]()
    assert src.size == 0 or not src.flags.c_contiguous
    before = src.tobytes()
    dst = np.full(src.nbytes, 0xA5, np.uint8)
    c_order_copy_into(dst, src, threads)
    want = np.ascontiguousarray(src)
    assert dst.tobytes() == want.reshape(-1).view(np.uint8).tobytes()
    assert not np.shares_memory(dst, src)
    assert src.tobytes() == before


def test_the_size_from_which_staging_turns_a_leaf_is_read_off_the_array():
    under, over = _CASES["under_the_constant"](), _CASES["over_the_constant"]()
    assert under.nbytes < RELAYOUT_MIN_BYTES <= over.nbytes
    assert array_preparer._relayout(under) is None
    assert array_preparer._relayout(np.ascontiguousarray(over)) is None
    assert array_preparer._relayout(over).tobytes() == np.ascontiguousarray(over).tobytes()


def test_two_copies_at_once_from_two_threads():
    srcs = [np.asfortranarray(_values((1100, 1300), np.float32, seed=s)) for s in (1, 2)]
    dsts = [np.empty(s.nbytes, np.uint8) for s in srcs]
    start = threading.Barrier(2)

    def run(i):
        start.wait()
        for _ in range(3):
            c_order_copy_into(dsts[i], srcs[i], 4)

    workers = [threading.Thread(target=run, args=(i,)) for i in range(2)]
    for w in workers:
        w.start()
    for w in workers:
        w.join()
    for src, dst in zip(srcs, dsts):
        assert dst.tobytes() == np.ascontiguousarray(src).tobytes()


def test_an_unsupported_dtype_is_refused():
    src = np.asfortranarray(np.zeros((4, 4), dtype="U3"))
    with pytest.raises(ValueError, match="Unsupported dtype"):
        c_order_copy_into(np.empty(src.nbytes, np.uint8), src)


# ------------------------------------------------------ through the take


class Sink(telemetry.MetricsSink):
    def __init__(self):
        self.spans, self.counters = [], {}

    def on_span_record(self, record):
        self.spans.append(record)

    def on_counter(self, name, delta, value):
        self.counters[name] = self.counters.get(name, 0) + delta


@pytest.fixture()
def fresh_pool():
    pool.clear()
    yield
    pool.clear()


def _state():
    big = np.asfortranarray(_values((1500, 1500), np.float32, seed=3))  # 9 MB, over the constant
    turned = _values((1200, 1024), ml_dtypes.bfloat16, seed=4).T  # under it
    plain = _values((1500, 1500), np.float32, seed=5)
    assert big.nbytes >= RELAYOUT_MIN_BYTES > turned.nbytes
    return {"big": big, "turned": turned, "plain": plain}


def _take(how, path, state):
    app = {"m": StateDict(**state)}
    with metrics_sink(Sink()) as sink:
        if how == "take":
            Snapshot.take(path, app)
        else:
            Snapshot.async_take(path, app).wait()
    return sink


@pytest.mark.parametrize("how", ["take", "async_take"])
def test_a_fortran_ordered_leaf_round_trips_and_its_relayout_is_told(
        tmp_path, monkeypatch, fresh_pool, how):
    """One ``relayout`` span (kind work, under ``stage.work``, with the
    leaf's bytes) and one bump of each counter for the one leaf over the
    constant; the strided leaf under it and the C-ordered one record
    none. The buffer is the pool's and goes back to it after the write."""
    monkeypatch.setenv("TPUSNAP_TELEMETRY", "1")
    monkeypatch.setenv("TPUSNAP_DISABLE_BATCHING", "1")
    state = _state()
    path = str(tmp_path / "snap")
    sink = _take(how, path, state)

    targets = {"m": StateDict(**{k: np.zeros(v.shape, v.dtype) for k, v in state.items()})}
    Snapshot(path).restore(targets)
    for name, want in state.items():
        got = targets["m"][name]
        assert got.dtype == want.dtype and got.shape == want.shape
        assert got.tobytes() == np.ascontiguousarray(want).tobytes(), name
    assert verify_snapshot(path).clean

    relayouts = [r for r in sink.spans if r.name == "relayout"]
    assert [r.attrs["bytes"] for r in relayouts] == [state["big"].nbytes]
    assert relayouts[0].kind == telemetry.WORK
    by_id = {r.id: r for r in sink.spans}
    assert by_id[relayouts[0].parent].name == "stage.work"
    assert sink.counters["stage.relayouts"] == 1
    assert sink.counters["stage.relayout_bytes"] == state["big"].nbytes
    # Nothing else of this take is staged through the pool (a numpy leaf of
    # an async take is written from live memory and verified after).
    assert pool.free_bytes() == state["big"].nbytes


def test_a_c_ordered_state_records_no_relayout(tmp_path, monkeypatch, fresh_pool):
    monkeypatch.setenv("TPUSNAP_TELEMETRY", "1")
    state = {"plain": _values((1500, 1500), np.float32, seed=6)}
    sink = _take("async_take", str(tmp_path / "snap"), state)
    assert not [r for r in sink.spans if r.name == "relayout"]
    assert "stage.relayouts" not in sink.counters and "stage.relayout_bytes" not in sink.counters
    assert pool.free_bytes() == 0


def test_the_second_take_turns_the_leaf_into_the_first_takes_buffer(tmp_path, fresh_pool):
    state = {"big": _state()["big"]}
    _take("async_take", str(tmp_path / "one"), state)
    hits = telemetry.counter_value("staging_pool.hits")
    _take("async_take", str(tmp_path / "two"), state)
    assert telemetry.counter_value("staging_pool.hits") == hits + 1
    assert pool.free_bytes() == state["big"].nbytes
    targets = {"m": StateDict(big=np.zeros_like(state["big"]))}
    Snapshot(str(tmp_path / "two")).restore(targets)
    assert targets["m"]["big"].tobytes() == state["big"].tobytes()


@pytest.mark.parametrize("mode", ["clone", "deferred_checksums_off", "compressed", "incremental"])
def test_every_staging_path_takes_the_turned_bytes(tmp_path, monkeypatch, fresh_pool, mode):
    """The other ways a leaf is staged (a defensive clone instead of
    copy-on-write, no checksums, the tile codec, an incremental take whose
    second save skips the unchanged leaf) read the relayout's buffer and
    hand it back to the pool where they stage other bytes."""
    if mode == "clone":
        monkeypatch.setenv("TPUSNAP_ASYNC_COW", "0")
    elif mode == "deferred_checksums_off":
        monkeypatch.setenv("TPUSNAP_DISABLE_CHECKSUM", "1")
    elif mode == "compressed":
        monkeypatch.setenv("TPUSNAP_COMPRESS", "lz4")
    big = np.asfortranarray(
        np.tile(np.arange(1500, dtype=np.float32), (1500, 1)) if mode == "compressed"
        else _values((1500, 1500), np.float32, seed=7))
    state = {"big": big}
    path = str(tmp_path / "snap")
    if mode == "incremental":
        base = str(tmp_path / "base")
        Snapshot.take(base, {"m": StateDict(**state)}, _record_dedup_hashes=True)
        Snapshot.async_take(path, {"m": StateDict(**state)}, incremental_from=base).wait()
    else:
        _take("async_take", path, state)
    targets = {"m": StateDict(big=np.zeros(big.shape, big.dtype))}
    Snapshot(path).restore(targets)
    assert targets["m"]["big"].tobytes() == np.ascontiguousarray(big).tobytes()
    assert verify_snapshot(path).clean
    assert pool.free_bytes() >= big.nbytes  # the turned bytes' buffer is back
