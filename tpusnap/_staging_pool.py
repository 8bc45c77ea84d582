"""Reusable aligned staging buffers for async-snapshot clones.

The async take's blocked window is dominated by the defensive clone on
CPU-backend hosts — and most of the CLONE's cost is not the copy but
first-touch page faults on the freshly allocated destination (the
kernel zeroes every 4 KiB page; ~1 GB/s on a single core here, measured
— vs ~3.5 GB/s for the copy into warm pages). A steady-state checkpoint
loop clones buffers of the SAME sizes every take, so this pool keeps
released clone buffers and hands them back warm: from the second async
take on, the blocked window pays the memcpy, not the kernel's page
zeroing.

Deliberately minimal: exact-size matching only (checkpoint loops stage
identical shapes every take), bounded by TPUSNAP_STAGING_POOL_BYTES
(default 4 GiB; 0 disables), and leak-proof — outstanding buffers are
tracked by weakref, so a buffer dropped on an abort path is simply
garbage-collected and forgotten instead of stranded. ``release`` is
safe to call with ANY buffer: non-pool buffers are ignored.
"""

from __future__ import annotations

import threading
import weakref
from typing import Dict, List, Tuple

import numpy as np

from . import knobs, telemetry

_lock = threading.Lock()
_free: List[Tuple[int, np.ndarray]] = []  # [(nbytes, buffer)]
_free_bytes = 0
# id(buffer) -> weakref: buffers handed out and not yet released. A
# weak ref (not strong) so abort paths leak nothing; dead entries are
# pruned on each acquire.
_outstanding: Dict[int, "weakref.ref"] = {}


def _cap_bytes() -> int:
    return knobs.get_staging_pool_bytes()


def acquire(nbytes: int) -> np.ndarray:
    """An aligned uint8 buffer of exactly ``nbytes`` — reused (warm
    pages) when a previously released buffer matches, fresh otherwise.
    Contents are undefined."""
    global _free_bytes
    from . import _native

    with _lock:
        # Prune outstanding entries whose buffers were dropped (aborts).
        dead = [k for k, r in _outstanding.items() if r() is None]
        for k in dead:
            del _outstanding[k]
        # Newest match first (LIFO): the most recently released buffer
        # has the warmest pages AND is what makes pipelined staging
        # windows allocation-free in steady state — window N+1's clone
        # of a recurring chunk size reuses the buffer window N's write
        # just released, so a whole multi-GB take touches only one
        # window's worth of distinct pages.
        for i in range(len(_free) - 1, -1, -1):
            n, buf = _free[i]
            if n == nbytes:
                _free.pop(i)
                _free_bytes -= n
                _outstanding[id(buf)] = weakref.ref(buf)
                telemetry.incr("staging_pool.hits")
                return buf
    buf = _native.aligned_empty(nbytes)
    with _lock:
        _outstanding[id(buf)] = weakref.ref(buf)
    return buf


def release(buf) -> bool:
    """Return a buffer to the pool; True when the pool RETAINED it.
    Retained bytes are bounded by TPUSNAP_STAGING_POOL_BYTES, a cache
    budget of its own — the write scheduler's memory budget governs
    in-flight staging buffers only and credits every write back in
    full (see execute_write_reqs). Ignores buffers the pool did not
    hand out (memoryviews of user state, slabs, ...). When the cap is
    exceeded the OLDEST free entries are evicted first, so a process
    whose staged sizes change (model resize, different snapshot
    contents) ages the stale sizes out instead of stranding them
    forever."""
    global _free_bytes
    if not isinstance(buf, np.ndarray):
        return False
    with _lock:
        ref = _outstanding.pop(id(buf), None)
        if ref is None or ref() is not buf:
            return False
        cap = _cap_bytes()
        if buf.nbytes > cap:
            return False
        while _free and _free_bytes + buf.nbytes > cap:
            old_n, _ = _free.pop(0)  # evict oldest
            _free_bytes -= old_n
        _free.append((buf.nbytes, buf))
        _free_bytes += buf.nbytes
        return True


def free_bytes() -> int:
    """Bytes currently RESIDENT in the free list (bounded by
    TPUSNAP_STAGING_POOL_BYTES)."""
    with _lock:
        return _free_bytes


def clear() -> None:
    """Drop all cached buffers (tests; memory-pressure escape hatch)."""
    global _free_bytes
    with _lock:
        _free.clear()
        _free_bytes = 0
