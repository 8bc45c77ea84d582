"""GCS plugin tests against a local fake HTTP server.

The reference gates its GCS tests behind a real bucket
(/root/reference/tests/test_gcs_storage_plugin.py); here a fake server
exercises the subtle paths deterministically, with fault injection:
resumable-upload chunking, 308 short-Range persistence forcing the
``bytes */total`` offset resync, 308-without-Range (no progress) retry,
transient-500 retry, collective-deadline expiry, and chunked ranged
download reassembly.
"""

import asyncio
import io
import json
import re
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

import tpusnap.storage_plugins.gcs as gcs_mod
from tpusnap.io_types import ReadIO, WriteIO
from tpusnap.storage_plugins.gcs import GCSStoragePlugin


class FakeGCS:
    """In-memory GCS fake speaking the JSON/upload API subset the plugin
    uses. Fault injection via the ``faults`` list: each entry is a dict
    consumed (in order) by the matching request kind:
      {"kind": "chunk", "action": "http500"}
      {"kind": "chunk", "action": "short", "keep": <bytes_of_this_chunk>}
      {"kind": "chunk", "action": "no_progress"}  # 308 without Range
      {"kind": "download", "action": "http500"}
    """

    def __init__(self):
        self.objects = {}
        self.sessions = {}  # sid -> {"name":, "data": bytearray, "total": int}
        self.faults = []
        self.request_log = []
        # Injected delay on upload requests (the cloud's round trip): each
        # is held until a second one is in flight, for at most
        # ``upload_hold_s``. ThreadingHTTPServer serves every request on
        # its own thread, so a client that keeps several uploads going
        # is released at once and a serial one shows a high-water mark
        # of 1, whatever the machine's speed. The journal and the
        # metadata are written alone by design and are not held.
        self.upload_hold_s = 0.0
        self.uploads_in_flight = 0
        self.uploads_high_water = 0
        self._next_sid = 0
        self._lock = threading.Lock()
        self._uploads = threading.Condition(self._lock)

    def upload_enter(self, name):
        with self._uploads:
            self.uploads_in_flight += 1
            if self.uploads_in_flight > self.uploads_high_water:
                self.uploads_high_water = self.uploads_in_flight
                self._uploads.notify_all()
            payload = ".tpusnap/" not in name and not name.endswith(
                ".snapshot_metadata"
            )
            if self.upload_hold_s and payload:
                self._uploads.wait_for(
                    lambda: self.uploads_high_water >= 2, self.upload_hold_s
                )

    def upload_exit(self):
        with self._uploads:
            self.uploads_in_flight -= 1

    def pop_fault(self, kind):
        with self._lock:
            for i, f in enumerate(self.faults):
                if f["kind"] == kind:
                    return self.faults.pop(i)
        return None


def _make_handler(state: FakeGCS):
    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, *args):  # silence
            pass

        def _reply(self, code, headers=None, body=b""):
            self.send_response(code)
            for k, v in (headers or {}).items():
                self.send_header(k, v)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            if body:
                self.wfile.write(body)

        def _read_body(self):
            n = int(self.headers.get("Content-Length", 0))
            return self.rfile.read(n) if n else b""

        def do_POST(self):
            state.request_log.append(("POST", self.path))
            body = self._read_body()
            from urllib.parse import unquote

            state.upload_enter(unquote(self.path.rpartition("name=")[2]))
            try:
                self._post(body)
            finally:
                state.upload_exit()

        def _post(self, body):
            m = re.match(r"/upload/storage/v1/b/([^/]+)/o\?uploadType=(\w+)&name=(.*)", self.path)
            if not m:
                return self._reply(404)
            from urllib.parse import unquote

            kind, name = m.group(2), unquote(m.group(3))
            if kind == "resumable":
                with state._lock:
                    sid = str(state._next_sid)
                    state._next_sid += 1
                    state.sessions[sid] = {
                        "name": name,
                        "data": bytearray(),
                    }
                host = self.headers["Host"]
                return self._reply(
                    200, {"Location": f"http://{host}/upload-session/{sid}"}
                )
            if kind == "media":
                state.objects[name] = bytes(body)
                return self._reply(200, body=b"{}")
            return self._reply(404)

        def do_PUT(self):
            state.request_log.append(("PUT", self.path, self.headers.get("Content-Range")))
            body = self._read_body()
            sess = state.sessions.get(self.path.rpartition("/")[2], {})
            state.upload_enter(sess.get("name", ""))
            try:
                self._put(body)
            finally:
                state.upload_exit()

        def _put(self, body):
            m = re.match(r"/upload-session/(\w+)", self.path)
            if not m:
                return self._reply(404)
            sess = state.sessions.get(m.group(1))
            if sess is None:
                return self._reply(404)
            crange = self.headers.get("Content-Range", "")
            probe = re.match(r"bytes \*/(\d+)", crange)
            if probe:
                # Status query: report persisted bytes. Never a fault target
                # (the plugin relies on it to resynchronize).
                persisted = len(sess["data"])
                if persisted and persisted == int(probe.group(1)):
                    state.objects[sess["name"]] = bytes(sess["data"])
                    return self._reply(200, body=b"{}")
                headers = (
                    {"Range": f"bytes=0-{persisted - 1}"} if persisted else {}
                )
                return self._reply(308, headers)
            m2 = re.match(r"bytes (\d+)-(\d+)/(\d+)", crange)
            if not m2:
                return self._reply(400)
            start, end, total = int(m2.group(1)), int(m2.group(2)), int(m2.group(3))
            fault = state.pop_fault("chunk")
            if fault:
                if fault["action"] == "http500":
                    return self._reply(500)
                if fault["action"] == "no_progress":
                    persisted = len(sess["data"])
                    headers = (
                        {"Range": f"bytes=0-{persisted - 1}"} if persisted else {}
                    )
                    # A stale header reporting no NEW progress; with zero
                    # persisted, omit Range entirely (the rawest form).
                    return self._reply(308, headers)
                if fault["action"] == "short":
                    keep = fault["keep"]
                    if start != len(sess["data"]):
                        return self._reply(503)
                    sess["data"].extend(body[:keep])
                    persisted = len(sess["data"])
                    headers = (
                        {"Range": f"bytes=0-{persisted - 1}"} if persisted else {}
                    )
                    return self._reply(308, headers)
            if start != len(sess["data"]):
                # Offset mismatch — the client must resync via a probe.
                return self._reply(503)
            sess["data"].extend(body)
            if end + 1 == total and len(sess["data"]) == total:
                state.objects[sess["name"]] = bytes(sess["data"])
                return self._reply(200, body=b"{}")
            return self._reply(308, {"Range": f"bytes=0-{len(sess['data']) - 1}"})

        def do_GET(self):
            state.request_log.append(("GET", self.path, self.headers.get("Range")))
            from urllib.parse import unquote

            m = re.match(r"/storage/v1/b/([^/]+)/o/([^?]+)(\?alt=media)?$", self.path)
            if not m:
                return self._reply(404)
            name = unquote(m.group(2))
            if name not in state.objects:
                return self._reply(404)
            data = state.objects[name]
            if m.group(3):  # media download
                fault = state.pop_fault("download")
                if fault and fault["action"] == "http500":
                    return self._reply(500)
                rng = self.headers.get("Range")
                if rng:
                    rm = re.match(r"bytes=(\d+)-(\d+)", rng)
                    lo, hi = int(rm.group(1)), int(rm.group(2))
                    return self._reply(206, body=data[lo : hi + 1])
                return self._reply(200, body=data)
            return self._reply(
                200, body=json.dumps({"size": len(data)}).encode()
            )

        def do_DELETE(self):
            from urllib.parse import unquote

            m = re.match(r"/storage/v1/b/([^/]+)/o/([^?]+)$", self.path)
            name = unquote(m.group(2))
            if name in state.objects:
                del state.objects[name]
                return self._reply(204)
            return self._reply(404)

    return Handler


@pytest.fixture()
def fake_gcs():
    state = FakeGCS()
    server = ThreadingHTTPServer(("127.0.0.1", 0), _make_handler(state))
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    state.endpoint = f"http://127.0.0.1:{server.server_address[1]}"
    try:
        yield state
    finally:
        server.shutdown()
        thread.join(timeout=5)


def _plugin(state, **options):
    opts = {"api_endpoint": state.endpoint, "deadline_sec": 30.0}
    opts.update(options)
    return GCSStoragePlugin("bkt/prefix", storage_options=opts)


def _run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


def test_round_trip_multi_chunk(fake_gcs, monkeypatch):
    monkeypatch.setattr(gcs_mod, "_UPLOAD_CHUNK_SIZE", 1000)
    plugin = _plugin(fake_gcs)
    payload = bytes(range(256)) * 20  # 5120 bytes -> 6 chunks
    _run(plugin.write(WriteIO(path="obj", buf=memoryview(payload))))
    assert fake_gcs.objects["prefix/obj"] == payload
    read_io = ReadIO(path="obj")
    _run(plugin.read(read_io))
    assert read_io.buf.getvalue() == payload
    _run(plugin.delete("obj"))
    assert "prefix/obj" not in fake_gcs.objects
    _run(plugin.close())


def test_empty_object(fake_gcs):
    plugin = _plugin(fake_gcs)
    _run(plugin.write(WriteIO(path="empty", buf=memoryview(b""))))
    assert fake_gcs.objects["prefix/empty"] == b""
    _run(plugin.close())


def test_short_range_forces_offset_resync(fake_gcs, monkeypatch):
    """A 308 persisting only part of a chunk: the client must accept the
    server's Range as authoritative and continue from there."""
    monkeypatch.setattr(gcs_mod, "_UPLOAD_CHUNK_SIZE", 1000)
    fake_gcs.faults.append({"kind": "chunk", "action": "short", "keep": 300})
    plugin = _plugin(fake_gcs)
    payload = bytes([i % 251 for i in range(3500)])
    _run(plugin.write(WriteIO(path="obj", buf=memoryview(payload))))
    assert fake_gcs.objects["prefix/obj"] == payload
    _run(plugin.close())


def test_http500_resyncs_via_probe(fake_gcs, monkeypatch):
    """Transient 500 mid-upload: retry must run the ``bytes */total``
    status probe and resume from the server's persisted offset."""
    monkeypatch.setattr(gcs_mod, "_UPLOAD_CHUNK_SIZE", 1000)
    fake_gcs.faults.append({"kind": "chunk", "action": "http500"})
    fake_gcs.faults.append({"kind": "chunk", "action": "http500"})
    plugin = _plugin(fake_gcs)
    payload = bytes([i % 241 for i in range(3500)])
    _run(plugin.write(WriteIO(path="obj", buf=memoryview(payload))))
    assert fake_gcs.objects["prefix/obj"] == payload
    probes = [
        r for r in fake_gcs.request_log if r[0] == "PUT" and r[2] and r[2].startswith("bytes */")
    ]
    assert probes, "500 recovery must consult the status probe"
    _run(plugin.close())


def test_no_progress_308_retries_then_succeeds(fake_gcs, monkeypatch):
    """A 308 with no Range header (nothing persisted) must count as a
    failed attempt — backoff, resync, then proceed."""
    monkeypatch.setattr(gcs_mod, "_UPLOAD_CHUNK_SIZE", 1000)
    fake_gcs.faults.append({"kind": "chunk", "action": "no_progress"})
    plugin = _plugin(fake_gcs)
    payload = bytes([i % 199 for i in range(2200)])
    _run(plugin.write(WriteIO(path="obj", buf=memoryview(payload))))
    assert fake_gcs.objects["prefix/obj"] == payload
    _run(plugin.close())


def test_collective_deadline_expiry_aborts(fake_gcs, monkeypatch):
    """A permanently wedged backend must abort once the collective
    deadline expires instead of retrying forever."""
    monkeypatch.setattr(gcs_mod, "_UPLOAD_CHUNK_SIZE", 1000)
    for _ in range(1000):
        fake_gcs.faults.append({"kind": "chunk", "action": "http500"})
    plugin = _plugin(fake_gcs, deadline_sec=1.5)
    payload = bytes(2000)
    with pytest.raises(Exception) as exc_info:
        _run(plugin.write(WriteIO(path="obj", buf=memoryview(payload))))
    assert "prefix/obj" not in fake_gcs.objects
    _run(plugin.close())


def test_chunked_ranged_download_reassembly(fake_gcs, monkeypatch):
    """Downloads larger than the chunk size are reassembled from multiple
    ranged GETs; explicit byte_range reads slice correctly."""
    monkeypatch.setattr(gcs_mod, "_DOWNLOAD_CHUNK_SIZE", 700)
    plugin = _plugin(fake_gcs)
    payload = bytes([i % 233 for i in range(5000)])
    fake_gcs.objects["prefix/obj"] = payload
    read_io = ReadIO(path="obj")
    _run(plugin.read(read_io))
    assert read_io.buf.getvalue() == payload
    media_gets = [r for r in fake_gcs.request_log if r[0] == "GET" and "alt=media" in r[1]]
    assert len(media_gets) >= 8  # 5000 / 700 -> 8 ranged chunks
    ranged = ReadIO(path="obj", byte_range=(123, 2600))
    _run(plugin.read(ranged))
    assert ranged.buf.getvalue() == payload[123:2600]
    _run(plugin.close())


def test_transient_download_500_retried(fake_gcs, monkeypatch):
    monkeypatch.setattr(gcs_mod, "_DOWNLOAD_CHUNK_SIZE", 700)
    fake_gcs.faults.append({"kind": "download", "action": "http500"})
    plugin = _plugin(fake_gcs)
    payload = bytes([i % 229 for i in range(2000)])
    fake_gcs.objects["prefix/obj"] = payload
    read_io = ReadIO(path="obj")
    _run(plugin.read(read_io))
    assert read_io.buf.getvalue() == payload
    _run(plugin.close())


def test_snapshot_end_to_end_against_fake_gcs(fake_gcs, monkeypatch):
    """Full Snapshot.take/restore through the gs:// scheme with faults."""
    import numpy as np

    from tpusnap import Snapshot, StateDict

    monkeypatch.setattr(gcs_mod, "_UPLOAD_CHUNK_SIZE", 4096)
    monkeypatch.setenv("STORAGE_EMULATOR_HOST", fake_gcs.endpoint)
    fake_gcs.faults.append({"kind": "chunk", "action": "http500"})
    fake_gcs.faults.append({"kind": "chunk", "action": "short", "keep": 1000})
    state = StateDict(
        w=np.arange(8192, dtype=np.float32), step=7, name="run1"
    )
    app_state = {"s": state}
    Snapshot.take("gs://bkt/snaps/s0", app_state)
    target = StateDict(
        w=np.zeros(8192, dtype=np.float32), step=0, name=""
    )
    app2 = {"s": target}
    Snapshot("gs://bkt/snaps/s0").restore(app2)
    assert np.array_equal(target["w"], state["w"])
    assert target["step"] == 7 and target["name"] == "run1"


def test_in_place_read_with_fused_crc(fake_gcs, monkeypatch):
    """ReadIO.into lands chunked downloads directly in the destination
    with the checksum accumulated chunk by chunk (the 7B-from-GCS
    restore path)."""
    import numpy as np

    from tpusnap import _native

    monkeypatch.setattr(gcs_mod, "_DOWNLOAD_CHUNK_SIZE", 1024)
    plugin = _plugin(fake_gcs)
    payload = bytes(range(256)) * 17  # 4352 bytes -> 5 download chunks
    _run(plugin.write(WriteIO(path="obj", buf=memoryview(payload))))

    dst = np.zeros(len(payload), dtype=np.uint8)
    read_io = ReadIO(path="obj", into=memoryview(dst), want_crc=True)
    _run(plugin.read(read_io))
    assert read_io.in_place
    assert dst.tobytes() == payload
    assert read_io.crc32c == _native.crc32c(payload)
    assert read_io.crc_algo == _native.checksum_algorithm()
    # generic buf view still works
    assert bytes(read_io.buf.getbuffer()) == payload

    # byte-ranged in-place read
    dst2 = np.zeros(2000, dtype=np.uint8)
    read_io = ReadIO(
        path="obj", byte_range=(100, 2100), into=memoryview(dst2), want_crc=True
    )
    _run(plugin.read(read_io))
    assert dst2.tobytes() == payload[100:2100]
    assert read_io.crc32c == _native.crc32c(payload[100:2100])
    _run(plugin.close())


def test_in_place_restore_end_to_end_gcs(fake_gcs, monkeypatch):
    """Snapshot restore through gs:// uses in-place reads for numpy
    targets; corruption in the bucket is detected."""
    import numpy as np

    from tpusnap import Snapshot, StateDict
    from tpusnap._native import ChecksumError

    monkeypatch.setenv("STORAGE_EMULATOR_HOST", fake_gcs.endpoint)
    arr = np.random.default_rng(0).standard_normal(50_000).astype(np.float32)
    Snapshot.take("gs://bkt/snaps/ip", {"s": StateDict(w=arr.copy())})
    target_arr = np.zeros_like(arr)
    Snapshot("gs://bkt/snaps/ip").restore({"s": StateDict(w=target_arr)})
    assert np.array_equal(target_arr, arr)

    # flip one byte of the stored blob in the bucket
    for name, blob in list(fake_gcs.objects.items()):
        if name.endswith("s/w") or "batched" in name:
            mutated = bytearray(blob)
            mutated[64] ^= 0xFF
            fake_gcs.objects[name] = bytes(mutated)
            break
    else:
        raise AssertionError(f"blob not found in {list(fake_gcs.objects)}")
    with pytest.raises(ChecksumError, match="w"):
        Snapshot("gs://bkt/snaps/ip").restore(
            {"s": StateDict(w=np.zeros_like(arr))}
        )


def test_scrub_verifies_and_detects_through_gcs(fake_gcs, monkeypatch):
    """verify_snapshot through gs:// exercises the non-in-place verify
    branch (the plugin fills ReadIO.buf; no fused read CRC), and must
    detect server-side bit rot."""
    import numpy as np

    from tpusnap import Snapshot, StateDict, verify_snapshot

    monkeypatch.setenv("STORAGE_EMULATOR_HOST", fake_gcs.endpoint)
    state = StateDict(w=np.arange(8192, dtype=np.float32), step=7)
    Snapshot.take("gs://bkt/snaps/scrub", {"s": state})
    opts = {"api_endpoint": fake_gcs.endpoint, "deadline_sec": 30.0}
    report = verify_snapshot("gs://bkt/snaps/scrub", storage_options=opts)
    assert report.clean and report.ok > 0

    # Flip a byte inside a stored blob on the "server".
    blob_names = [
        k for k in fake_gcs.objects if not k.endswith(".snapshot_metadata")
    ]
    assert blob_names
    name = max(blob_names, key=lambda k: len(fake_gcs.objects[k]))
    data = bytearray(fake_gcs.objects[name])
    data[10] ^= 0xFF
    fake_gcs.objects[name] = bytes(data)
    report = verify_snapshot("gs://bkt/snaps/scrub", storage_options=opts)
    assert not report.clean
    assert report.corrupt >= 1


def test_incremental_snapshot_through_gcs(fake_gcs, monkeypatch):
    """Cross-snapshot '../base/...' references resolve through the gs://
    key namespace (client-side normpath in _object_name)."""
    import numpy as np

    from tpusnap import Snapshot, StateDict, verify_snapshot
    from tpusnap.knobs import override_batching_disabled

    monkeypatch.setenv("STORAGE_EMULATOR_HOST", fake_gcs.endpoint)
    opts = {"api_endpoint": fake_gcs.endpoint, "deadline_sec": 30.0}
    state = StateDict(w=np.arange(8192, dtype=np.float32), step=1)
    with override_batching_disabled(True):
        Snapshot.take("gs://bkt/snaps/s0", {"s": state})
        n_before = len(fake_gcs.objects)
        Snapshot.take(
            "gs://bkt/snaps/s1",
            {"s": state},
            incremental_from="gs://bkt/snaps/s0",
        )
    # Only s1's metadata (plus the telemetry sidecar) was uploaded; w
    # deduped against s0's blob — no payload bytes moved.
    new = {
        k
        for k in fake_gcs.objects
        if "snaps/s1" in k and ".tpusnap/" not in k
    }
    assert new == {"snaps/s1/.snapshot_metadata"}, new
    n_sidecars = sum(
        1 for k in fake_gcs.objects if "snaps/s1" in k and ".tpusnap/" in k
    )
    assert len(fake_gcs.objects) == n_before + 1 + n_sidecars
    target = StateDict(w=np.zeros(8192, dtype=np.float32), step=0)
    Snapshot("gs://bkt/snaps/s1", storage_options=opts).restore({"s": target})
    assert np.array_equal(target["w"], state["w"]) and target["step"] == 1
    assert verify_snapshot("gs://bkt/snaps/s1", storage_options=opts).clean


def test_materialize_through_gcs(fake_gcs, monkeypatch):
    """materialize copies base blobs within the gs:// namespace and
    rewrites the manifest; the base can then be deleted server-side."""
    import numpy as np

    from tpusnap import Snapshot, StateDict, verify_snapshot
    from tpusnap.inspect import materialize_snapshot
    from tpusnap.knobs import override_batching_disabled

    monkeypatch.setenv("STORAGE_EMULATOR_HOST", fake_gcs.endpoint)
    opts = {"api_endpoint": fake_gcs.endpoint, "deadline_sec": 30.0}
    state = StateDict(w=np.arange(8192, dtype=np.float32), step=1)
    with override_batching_disabled(True):
        Snapshot.take("gs://bkt/snaps/m0", {"s": state})
        Snapshot.take(
            "gs://bkt/snaps/m1",
            {"s": state},
            incremental_from="gs://bkt/snaps/m0",
        )
    stats = materialize_snapshot("gs://bkt/snaps/m1", storage_options=opts)
    assert stats["blobs_copied"] == 1
    # Delete the base server-side; the materialized snapshot stands alone.
    for k in list(fake_gcs.objects):
        if "snaps/m0" in k:
            del fake_gcs.objects[k]
    assert verify_snapshot("gs://bkt/snaps/m1", storage_options=opts).clean
    target = StateDict(w=np.zeros(8192, dtype=np.float32), step=0)
    Snapshot("gs://bkt/snaps/m1", storage_options=opts).restore({"s": target})
    assert np.array_equal(target["w"], state["w"]) and target["step"] == 1


def test_take_keeps_several_uploads_in_flight(fake_gcs, monkeypatch):
    """Against a store that answers slowly, what the framework controls
    is how many requests it keeps going: a take of 16 x 1 MiB through
    ``gs://`` has more than one upload in flight at once (counted by the
    fake, which holds each upload until a second arrives), in sessions
    of several chunks, and restores bit for bit."""
    import numpy as np

    from tpusnap import PytreeState, Snapshot
    from tpusnap.knobs import override_batching_disabled

    monkeypatch.setattr(gcs_mod, "_UPLOAD_CHUNK_SIZE", 256 << 10)
    monkeypatch.setattr(gcs_mod, "_DOWNLOAD_CHUNK_SIZE", 256 << 10)
    monkeypatch.setenv("STORAGE_EMULATOR_HOST", fake_gcs.endpoint)
    fake_gcs.upload_hold_s = 20.0
    rng = np.random.default_rng(0)
    state = {
        f"w{i}": rng.integers(0, 255, 1 << 20, dtype=np.uint8)
        for i in range(16)
    }
    with override_batching_disabled(True):
        Snapshot.take("gs://bkt/snaps/pipe", {"m": PytreeState(state)})
    assert fake_gcs.uploads_high_water > 1
    assert fake_gcs.uploads_in_flight == 0
    chunk_puts = [
        r for r in fake_gcs.request_log
        if r[0] == "PUT" and r[2] and not r[2].startswith("bytes */")
    ]
    assert len(chunk_puts) >= 16 * 4
    target = PytreeState({k: np.zeros_like(v) for k, v in state.items()})
    Snapshot("gs://bkt/snaps/pipe").restore({"m": target})
    for k, v in state.items():
        assert np.array_equal(target.tree[k], v), k
