"""Zero-copy serialization between host arrays and byte buffers.

TPU-native counterpart of the reference's serialization module
(/root/reference/torchsnapshot/serialization.py:32-254). Differences by
design:

- dtypes are JAX/numpy dtypes (incl. ``bfloat16`` and the fp8 family via
  ``ml_dtypes``) instead of torch dtypes; there is no quantized-tensor
  format because XLA has no quantized tensor objects — int4/int8 arrays
  cover that ground.
- Every fixed-width dtype takes the zero-copy buffer-protocol path. numpy
  has no native bf16/fp8 buffer format, so those are byte-reinterpreted
  through a same-itemsize unsigned-int view (the same idea as the
  reference's untyped-storage workaround, serialization.py:186-233) —
  no value conversion ever happens, so restores are bit-identical.
- The fallback serializer for arbitrary Python objects is stdlib pickle
  (the reference's ``torch.save`` is pickle underneath too).
"""

from __future__ import annotations

import pickle
from concurrent.futures import ThreadPoolExecutor
from enum import Enum
from typing import Any, Sequence, Tuple

import ml_dtypes
import numpy as np


class Serializer(Enum):
    BUFFER_PROTOCOL = "buffer_protocol"
    PICKLE = "pickle"


# Canonical dtype-string table. Keys are what lands in TensorEntry.dtype.
SUPPORTED_DTYPES = {
    "float64": np.dtype("float64"),
    "float32": np.dtype("float32"),
    "float16": np.dtype("float16"),
    "bfloat16": np.dtype(ml_dtypes.bfloat16),
    "float8_e4m3fn": np.dtype(ml_dtypes.float8_e4m3fn),
    "float8_e5m2": np.dtype(ml_dtypes.float8_e5m2),
    "complex128": np.dtype("complex128"),
    "complex64": np.dtype("complex64"),
    "int64": np.dtype("int64"),
    "int32": np.dtype("int32"),
    "int16": np.dtype("int16"),
    "int8": np.dtype("int8"),
    "uint64": np.dtype("uint64"),
    "uint32": np.dtype("uint32"),
    "uint16": np.dtype("uint16"),
    "uint8": np.dtype("uint8"),
    "bool": np.dtype("bool"),
}

_DTYPE_TO_STRING = {v: k for k, v in SUPPORTED_DTYPES.items()}

# dtypes numpy's buffer protocol can't describe; bytes are reinterpreted
# through a same-itemsize unsigned view instead (never converted).
_BYTE_VIEW_DTYPES = {
    "bfloat16": np.dtype("uint16"),
    "float8_e4m3fn": np.dtype("uint8"),
    "float8_e5m2": np.dtype("uint8"),
}


def dtype_to_string(dtype: Any) -> str:
    """Canonical string for a numpy/jax dtype (e.g. ``"bfloat16"``)."""
    np_dtype = np.dtype(dtype)
    try:
        return _DTYPE_TO_STRING[np_dtype]
    except KeyError:
        raise ValueError(f"Unsupported dtype: {dtype}") from None


def string_to_dtype(s: str) -> np.dtype:
    try:
        return SUPPORTED_DTYPES[s]
    except KeyError:
        raise ValueError(f"Unsupported dtype string: {s}") from None


def dtype_itemsize(s: str) -> int:
    return string_to_dtype(s).itemsize


def tensor_nbytes(dtype_str: str, shape: Sequence[int]) -> int:
    n = dtype_itemsize(dtype_str)
    for dim in shape:
        n *= dim
    return n


def _byte_compatible_view(arr: np.ndarray) -> np.ndarray:
    """Reinterpret custom dtypes as buffer-protocol-compatible ones."""
    dtype_str = _DTYPE_TO_STRING.get(arr.dtype)
    if dtype_str in _BYTE_VIEW_DTYPES:
        return arr.view(_BYTE_VIEW_DTYPES[dtype_str])
    return arr


def array_as_memoryview(arr: np.ndarray) -> memoryview:
    """Zero-copy flat byte view of a host array (contiguous; no conversion).

    Counterpart of reference ``tensor_as_memoryview``
    (serialization.py:162-233). Non-contiguous inputs are copied once.
    """
    if arr.dtype not in _DTYPE_TO_STRING:
        raise ValueError(f"Unsupported dtype: {arr.dtype}")
    if not arr.flags.c_contiguous:
        arr = np.ascontiguousarray(arr)
    arr = _byte_compatible_view(arr)
    return memoryview(arr).cast("B", (arr.nbytes,)) if arr.nbytes else memoryview(b"")


# A host array in another order than C is turned piece by piece: pieces
# of about this many bytes, cut along the axis that the source's strides
# make its outermost, so that a piece reads one compact block of the
# source (it stays in a core's cache while it is turned) and writes runs
# of the destination. On the chip machine's host (PR 32, 13 cores; a
# [2560, 18992] float32 with strides (4, 10240), 194 MB, into warm pages,
# four threads): pieces of 0.5 / 1 / 2 / 4 / 8 MiB take 50 / 39 / 36 / 33 /
# 33 ms, cut along the other axis 76 / 70 / 69 / 65 / 62 ms; one thread
# 118 ms, eight 19 ms; np.ascontiguousarray 227 ms, 444 ms into fresh pages.
_RELAYOUT_PIECE_BYTES = 2 << 20
# An array under this size keeps np.ascontiguousarray: starting and
# joining four threads takes 2.6 ms on that host, which is what
# np.ascontiguousarray takes for 4-5 MiB there (0.56 ms a MiB).
RELAYOUT_MIN_BYTES = 8 << 20


def c_order_copy_into(dst: Any, arr: np.ndarray, nthreads: int = 1) -> None:
    """Write ``arr``'s elements in C order into ``dst``, a writable
    contiguous buffer of ``arr.nbytes`` bytes that does not overlap it:
    what ``np.ascontiguousarray`` would give, bit for bit, for any
    strides. The pieces are copied by up to ``nthreads`` threads, the
    GIL released while a piece is copied; the caller keeps ``arr``
    alive until this returns."""
    if arr.dtype not in _DTYPE_TO_STRING:
        raise ValueError(f"Unsupported dtype: {arr.dtype}")
    src = _byte_compatible_view(arr)
    out = np.frombuffer(dst, dtype=src.dtype).reshape(src.shape)
    if src.ndim == 0 or src.size == 0:
        np.copyto(out, src)
        return
    axis = max(
        range(src.ndim),
        key=lambda a: abs(src.strides[a]) if src.shape[a] > 1 else -1,
    )
    n = src.shape[axis]
    per = max(1, _RELAYOUT_PIECE_BYTES * n // src.nbytes)

    def turn(lo: int) -> None:
        piece = (slice(None),) * axis + (slice(lo, lo + per),)
        np.copyto(out[piece], src[piece])

    starts = range(0, n, per)
    workers = min(nthreads, len(starts))
    if workers <= 1:
        for lo in starts:
            turn(lo)
        return
    with ThreadPoolExecutor(workers, thread_name_prefix="tpusnap-relayout") as pool:
        list(pool.map(turn, starts))


def array_from_memoryview(
    mv: memoryview, dtype: str, shape: Sequence[int]
) -> np.ndarray:
    """Zero-copy array over a byte buffer (counterpart of reference
    ``tensor_from_memoryview``, serialization.py:236-244). The result
    aliases ``mv`` and is read-only if ``mv`` is."""
    np_dtype = string_to_dtype(dtype)
    view_dtype = _BYTE_VIEW_DTYPES.get(dtype, np_dtype)
    arr = np.frombuffer(mv, dtype=view_dtype)
    if view_dtype is not np_dtype:
        arr = arr.view(np_dtype)
    return arr.reshape(tuple(shape))


def pickle_as_bytes(obj: Any) -> bytes:
    """Object fallback serializer (reference torch_save_as_bytes,
    serialization.py:247-250)."""
    return pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL)


def pickle_from_bytes(buf: bytes) -> Any:
    return pickle.loads(buf)


def per_element_sizes() -> Tuple[str, ...]:
    return tuple(SUPPORTED_DTYPES)
