"""KV stores + LinearBarrier for thread-safe coordination.

Counterpart of /root/reference/torchsnapshot/dist_store.py. The async
snapshot commit runs on a background thread where collectives are
forbidden (reference snapshot.py:902), so it synchronizes through a KV
store instead:

- ``CoordinationKVStore`` — the jax.distributed coordination-service
  client (the TPU-native replacement for c10d TCPStore).
- ``FileKVStore`` — a shared-filesystem store for single-host
  multi-process tests (and a fallback when no coordination service is
  up but ranks share a filesystem).
- ``LinearBarrier`` — the reference's two-phase (arrive/depart) barrier
  with error propagation (dist_store.py:91-196): any rank can
  ``report_error``; every waiter then re-raises it, which is how a
  failed async snapshot aborts the metadata commit on all ranks.
"""

from __future__ import annotations

import abc
import logging
import os
import pickle
import tempfile
import time
from typing import Callable, List, Optional

logger = logging.getLogger(__name__)

_POLL_INTERVAL_SEC = 0.05


def _default_timeout_sec() -> float:
    # Historically a 600.0 literal; now the TPUSNAP_BARRIER_TIMEOUT_S
    # knob, resolved per-wait so test overrides apply without reimports.
    from .knobs import get_barrier_timeout_s

    return get_barrier_timeout_s()


class KVStore(abc.ABC):
    @abc.abstractmethod
    def set(self, key: str, value: bytes) -> None: ...

    @abc.abstractmethod
    def try_get(self, key: str) -> Optional[bytes]: ...

    def try_get_dir(self, prefix: str) -> Optional[dict]:
        """All (key, value) pairs under ``prefix`` in ONE call when the
        backend supports it, else None (caller falls back to per-key
        gets). Keys in the result are relative to the store, like the
        keys passed to ``set``."""
        return None

    def delete_prefix(self, prefix: str) -> None:
        """Best-effort deletion of every key under ``prefix``."""

    def get(self, key: str, timeout_sec: Optional[float] = None) -> bytes:
        if timeout_sec is None:
            timeout_sec = _default_timeout_sec()
        deadline = time.monotonic() + timeout_sec
        while True:
            value = self.try_get(key)
            if value is not None:
                return value
            if time.monotonic() > deadline:
                raise TimeoutError(f"Timed out waiting for key {key!r}")
            time.sleep(_POLL_INTERVAL_SEC)


def _client_try_get(client, full_key: str):
    """Non-blocking single-key get against the coordination client.
    Returns None when the key is absent (or the service errored)."""
    try:
        return client.key_value_try_get(full_key)
    except Exception:
        return None


class CoordinationKVStore(KVStore):
    """Backed by the jax.distributed coordination service client."""

    def __init__(self, prefix: str = "tpusnap_store") -> None:
        from jax._src import distributed

        client = distributed.global_state.client
        if client is None:
            raise RuntimeError("jax.distributed is not initialized")
        self._client = client
        self._prefix = prefix

    def _k(self, key: str) -> str:
        return f"{self._prefix}/{key}"

    def set(self, key: str, value: bytes) -> None:
        import base64

        payload = base64.b64encode(value).decode()
        # Overwrite semantics: lease/heartbeat republishes and
        # elastic-stream membership transitions rewrite the SAME key —
        # the coordination service's default insert-only key_value_set
        # rejects the second write (ALREADY_EXISTS).
        self._client.key_value_set(self._k(key), payload, allow_overwrite=True)

    def try_get(self, key: str) -> Optional[bytes]:
        import base64

        raw = _client_try_get(self._client, self._k(key))
        if raw is None:
            return None
        if isinstance(raw, bytes):
            raw = raw.decode()
        return base64.b64decode(raw)

    def try_get_dir(self, prefix: str) -> Optional[dict]:
        import base64

        try:
            pairs = self._client.key_value_dir_get(self._k(prefix))
        except Exception:
            return None
        out = {}
        want = self._prefix + "/"
        for k, v in pairs:
            if isinstance(v, bytes):
                v = v.decode()
            # Defensive stripping: the coordination service is only
            # OBSERVED to return keys exactly as set; verify the prefix
            # instead of blind slicing (tolerating a leading slash), and
            # report "no dir support" on any unexpected shape so callers
            # take their per-key fallback rather than consuming
            # silently corrupted relative keys.
            rel_key = k.lstrip("/")
            if not rel_key.startswith(want):
                return None
            out[rel_key[len(want) :]] = base64.b64decode(v)
        return out

    def delete_prefix(self, prefix: str) -> None:
        try:
            self._client.key_value_delete(self._k(prefix))
        except Exception:
            # Best-effort cleanup; a leaked key costs service memory only.
            logger.debug(
                "KV delete_prefix(%r) failed", prefix, exc_info=True
            )


class FileKVStore(KVStore):
    """Directory-backed store; atomic via rename. Works wherever ranks
    share a filesystem (incl. the snapshot destination itself)."""

    def __init__(self, root: str) -> None:
        self.root = root
        os.makedirs(root, exist_ok=True)

    def _path(self, key: str) -> str:
        return os.path.join(self.root, key.replace("/", "%2F"))

    def set(self, key: str, value: bytes) -> None:
        path = self._path(key)
        fd, tmp = tempfile.mkstemp(dir=self.root)
        try:
            with os.fdopen(fd, "wb") as f:
                f.write(value)
            os.replace(tmp, path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise

    def try_get(self, key: str) -> Optional[bytes]:
        try:
            with open(self._path(key), "rb") as f:
                return f.read()
        except FileNotFoundError:
            return None

    def try_get_dir(self, prefix: str) -> Optional[dict]:
        enc = prefix.replace("/", "%2F")
        out = {}
        for name in os.listdir(self.root):
            if name.startswith(enc):
                with open(os.path.join(self.root, name), "rb") as f:
                    out[name.replace("%2F", "/")] = f.read()
        return out

    def delete_prefix(self, prefix: str) -> None:
        enc = prefix.replace("/", "%2F")
        for name in os.listdir(self.root):
            if name.startswith(enc):
                try:
                    os.unlink(os.path.join(self.root, name))
                except OSError:
                    pass


class MemoryKVStore(KVStore):
    """In-process store for single-process operation and unit tests."""

    def __init__(self) -> None:
        self._data = {}

    def set(self, key: str, value: bytes) -> None:
        self._data[key] = value

    def try_get(self, key: str) -> Optional[bytes]:
        return self._data.get(key)

    def try_get_dir(self, prefix: str) -> Optional[dict]:
        return {
            k: v for k, v in self._data.items() if k.startswith(prefix)
        }

    def delete_prefix(self, prefix: str) -> None:
        for k in [k for k in self._data if k.startswith(prefix)]:
            del self._data[k]


class LinearBarrierError(RuntimeError):
    pass


class TakeAbortedError(RuntimeError):
    """Another rank's take failed: its abort record was published through
    the coordination KV store, and this rank's barrier/commit wait raised
    within seconds instead of burning the full barrier timeout. The path
    is reusable — no ``.snapshot_metadata`` was written, and each rank
    best-effort deleted its staged blobs."""


class TakeAbortMonitor:
    """Distributed take-abort propagation over the coordination KV store.

    When any rank's take fails, it ``publish``es an abort record under a
    take-scoped prefix; every other rank's waits (polling commit
    barriers, the background commit's LinearBarrier) run ``check`` as a
    watcher and raise :class:`TakeAbortedError` within
    ``check_interval_sec`` + one poll interval. Records are left behind
    on abort (take-scoped keys, a few bytes; the next take uses a fresh
    take_id) and the prefix is deleted on a successful commit."""

    _PREFIX = "tpusnap_abort"

    def __init__(
        self,
        store: KVStore,
        take_id: str,
        rank: int,
        check_interval_sec: float = 0.25,
    ) -> None:
        self._store = store
        self.take_id = take_id
        self.rank = rank
        self._interval = check_interval_sec
        self._last_check = 0.0
        self._published = False

    def _prefix(self) -> str:
        return f"{self._PREFIX}/{self.take_id}/"

    def publish(self, exc: BaseException) -> None:
        """Record this rank's failure for every peer to observe."""
        if self._published:
            return
        self._published = True
        try:
            payload = pickle.dumps(exc)
        except Exception:
            payload = pickle.dumps(RuntimeError(repr(exc)))
        try:
            self._store.set(f"{self._prefix()}r{self.rank}", payload)
        except Exception:
            logger.warning(
                "Failed to publish take-abort record for take %s",
                self.take_id,
                exc_info=True,
            )

    def mark_commit_started(self) -> None:
        """Committing-rank flag set right before the metadata write.
        Aborting ranks consult it: once the commit may exist, staged
        blobs must NOT be deleted (a committed manifest references
        them — orphan blobs are safe, dangling references are not)."""
        try:
            self._store.set(f"{self._prefix()}commit_started", b"1")
        except Exception:
            # Swallowed deliberately, but not silent: if the flag never
            # lands, aborting peers fall back to commit_may_have_started's
            # conservative True and keep their staged blobs.
            logger.debug(
                "commit_started flag publish failed for take %s",
                self.take_id,
                exc_info=True,
            )

    def commit_may_have_started(self) -> bool:
        try:
            return (
                self._store.try_get(f"{self._prefix()}commit_started")
                is not None
            )
        except Exception:
            # Unknown — be conservative and keep the blobs.
            return True

    def check(self, force: bool = False) -> None:
        """Raise :class:`TakeAbortedError` if any rank published an abort
        record. RPC-throttled to ``check_interval_sec`` unless forced."""
        now = time.monotonic()
        if not force and now - self._last_check < self._interval:
            return
        self._last_check = now
        try:
            records = self._store.try_get_dir(self._prefix())
        except Exception:
            return
        if not records:
            return
        # try_get_dir keys are store-relative (they include the prefix).
        prefix = self._prefix()
        aborts = sorted(
            (k[len(prefix) :], v)
            for k, v in records.items()
            if k.startswith(prefix) and k[len(prefix) :].startswith("r")
        )
        if not aborts:
            return
        rank_key, payload = aborts[0]
        try:
            cause: Optional[BaseException] = pickle.loads(payload)
        except Exception:
            cause = None
        err = TakeAbortedError(
            f"take {self.take_id} aborted by rank {rank_key[1:]}: {cause!r}"
        )
        if cause is not None:
            raise err from cause
        raise err

    def clear(self) -> None:
        """Best-effort deletion of the take's abort prefix (leader calls
        this after a successful commit so the service does not accumulate
        per-take keys)."""
        try:
            self._store.delete_prefix(self._prefix())
        except Exception:
            logger.debug(
                "abort-prefix cleanup failed for take %s",
                self.take_id,
                exc_info=True,
            )


class LinearBarrier:
    """Two-phase barrier with error propagation (reference
    dist_store.py:91-196). Leader waits for every rank to arrive, then
    signals departure. ``report_error`` poisons the barrier: all waiters
    raise. ``watchers`` are callables run every poll iteration that may
    raise to abort the wait early (take-abort propagation). Pure KV
    traffic — safe from non-main threads.

    ``ranks`` restricts membership to a subset of the world (default:
    every rank) — the degraded-commit path synchronizes the SURVIVOR
    set of a take whose dead rank will never arrive; the leader defaults
    to the smallest member."""

    def __init__(
        self,
        store: KVStore,
        prefix: str,
        rank: int,
        world_size: int,
        leader_rank: Optional[int] = None,
        timeout_sec: Optional[float] = None,
        watchers: Optional[List[Callable[[], None]]] = None,
        ranks: Optional[List[int]] = None,
    ) -> None:
        self.store = store
        self.prefix = prefix
        self.rank = rank
        self.world_size = world_size
        self.ranks = (
            sorted(ranks) if ranks is not None else list(range(world_size))
        )
        if rank not in self.ranks:
            raise ValueError(
                f"LinearBarrier {prefix!r}: rank {rank} is not a member of "
                f"{self.ranks}"
            )
        self.leader_rank = (
            leader_rank if leader_rank is not None else min(self.ranks)
        )
        self.timeout_sec = (
            timeout_sec if timeout_sec is not None else _default_timeout_sec()
        )
        self.watchers = list(watchers or [])
        # True while blocked inside a _checked_get poll loop — read by
        # current_missing() from the stall watchdog thread.
        self._in_wait = False

    def _key(self, *parts: str) -> str:
        return "/".join((self.prefix,) + parts)

    def _raise_any_reported_error(self) -> None:
        """One dir-get over the error prefix when the backend supports it
        (coordination clients without a cheap single-key probe pay a
        blocking-get timeout PER missing key — O(world_size) per poll
        iteration scales badly); per-key scan as the fallback."""
        prefix = self._key("error") + "/"
        try:
            errs = self.store.try_get_dir(prefix)
        except Exception:
            errs = None
        if errs is None:
            errs = {}
            for r in self.ranks:
                err = self.store.try_get(self._key("error", str(r)))
                if err is not None:
                    errs[str(r)] = err
        for k, err in sorted(errs.items()):
            rank = k.rsplit("/", 1)[-1]
            raise LinearBarrierError(
                f"Rank {rank} reported error: {pickle.loads(err)}"
            )

    def _checked_get(self, key: str) -> bytes:
        """Wait for a key while also watching for reported errors."""
        deadline = time.monotonic() + self.timeout_sec
        self._in_wait = True
        try:
            while True:
                value = self.store.try_get(key)
                if value is not None:
                    return value
                for watcher in self.watchers:
                    watcher()
                self._raise_any_reported_error()
                if time.monotonic() > deadline:
                    raise TimeoutError(
                        f"LinearBarrier {self.prefix!r}: timed out waiting for "
                        f"{key!r}"
                    )
                time.sleep(_POLL_INTERVAL_SEC)
        finally:
            self._in_wait = False

    def current_missing(self) -> Optional[List[int]]:
        """While a rank is blocked in this barrier, the sorted rank ids
        that have NOT arrived (stall-watchdog attribution; a non-leader
        stuck in depart() with every rank arrived gets the leader, which
        owns the pending depart signal). None when not waiting. KV reads
        only — safe from the watchdog thread."""
        if not self._in_wait:
            return None
        missing = []
        for r in self.ranks:
            try:
                if self.store.try_get(self._key("arrive", str(r))) is None:
                    missing.append(r)
            except Exception:
                return None
        if not missing:
            return [self.leader_rank] if self.rank != self.leader_rank else None
        return missing

    def arrive(self) -> None:
        from . import flight, telemetry

        flight.record("barrier_enter", op=self.prefix)
        with telemetry.span("kv.barrier_arrive", kind=telemetry.WAIT):
            self.store.set(self._key("arrive", str(self.rank)), b"1")
            if self.rank == self.leader_rank:
                for r in self.ranks:
                    self._checked_get(self._key("arrive", str(r)))

    def depart(self) -> None:
        from . import flight, telemetry

        with telemetry.span("kv.barrier_depart", kind=telemetry.WAIT):
            if self.rank == self.leader_rank:
                self.store.set(self._key("depart"), b"1")
            else:
                self._checked_get(self._key("depart"))
        # Release observed: the cross-rank skew anchor (every rank logs
        # the same prefix within one poll interval of the leader's
        # depart signal).
        flight.record("barrier_exit", op=self.prefix)

    def report_error(self, exc: BaseException) -> None:
        try:
            payload = pickle.dumps(exc)
        except Exception:
            payload = pickle.dumps(RuntimeError(repr(exc)))
        self.store.set(self._key("error", str(self.rank)), payload)
