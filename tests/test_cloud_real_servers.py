"""Cloud storage against REAL server binaries (ROADMAP 5c evidence).

``test_gcs.py``/``test_s3.py`` exercise the plugins against in-process
stubs — fast and deterministic, but the stub only speaks the API subset
its author remembered. This module runs the same plugin + snapshot
round trips against the real ``fake-gcs-server`` and ``minio`` SERVER
BINARIES when they are on PATH (opt-in evidence: each suite skips
cleanly when its binary — or its client package — is missing, so no CI
lane ever fails for lacking them). The CI gate script runs the
``cloud_real`` marker as an optional step whenever a binary is found.

Server processes are spawned per module, on ephemeral ports, with
filesystem state under pytest's tmp dirs; readiness is polled over the
servers' own health endpoints instead of sleeps.
"""

import os
import shutil
import subprocess
import sys
import time
import uuid

import numpy as np
import pytest

from tpusnap import Snapshot, StateDict, verify_snapshot
from tpusnap.io_types import ReadIO, WriteIO
from tpusnap.test_utils import find_free_port

_GCS_BINARY = shutil.which("fake-gcs-server")
_MINIO_BINARY = shutil.which("minio")

_MINIO_USER = "tpusnap-ci"
_MINIO_PASSWORD = "tpusnap-ci-secret"


def _wait_http_ready(url: str, timeout_s: float = 30.0) -> None:
    import urllib.error
    import urllib.request

    deadline = time.monotonic() + timeout_s
    last = None
    while time.monotonic() < deadline:
        try:
            with urllib.request.urlopen(url, timeout=2) as resp:
                if resp.status < 500:
                    return
        except urllib.error.HTTPError as e:
            if e.code < 500:
                return  # the server answered; 4xx is fine for readiness
            last = e
        except Exception as e:  # noqa: BLE001 - retried until deadline
            last = e
        time.sleep(0.2)
    raise RuntimeError(f"server at {url} never became ready: {last}")


def _terminate(proc: subprocess.Popen) -> None:
    proc.terminate()
    try:
        proc.wait(timeout=10)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait(timeout=10)


def _plugin_round_trip(url: str, storage_options) -> None:
    import asyncio

    from tpusnap.storage_plugin import url_to_storage_plugin_in_event_loop

    loop = asyncio.new_event_loop()
    plugin = url_to_storage_plugin_in_event_loop(url, loop, storage_options)
    try:
        payload = np.arange(100_000, dtype=np.uint8).tobytes()
        plugin.sync_write(WriteIO(path="blob", buf=payload), loop)
        read_io = ReadIO(path="blob")
        plugin.sync_read(read_io, loop)
        assert read_io.buf.getvalue() == payload
        ranged = ReadIO(path="blob", byte_range=(10, 50))
        plugin.sync_read(ranged, loop)
        assert ranged.buf.getvalue() == payload[10:50]
        loop.run_until_complete(plugin.delete("blob"))
    finally:
        plugin.sync_close(loop)
        loop.close()


def _snapshot_round_trip(url: str, storage_options) -> None:
    state = StateDict(
        w=np.random.default_rng(0).standard_normal((256, 32)).astype(np.float32),
        step=7,
    )
    Snapshot.take(url, {"app": state}, storage_options=storage_options)
    assert verify_snapshot(url, storage_options=storage_options).clean
    target = {"app": StateDict(w=np.zeros((256, 32), np.float32), step=0)}
    Snapshot(url, storage_options=storage_options).restore(target)
    assert target["app"]["step"] == 7
    assert np.array_equal(target["app"]["w"], state["w"])


# ------------------------------------------------------- fake-gcs-server


@pytest.fixture(scope="module")
def fake_gcs_endpoint(tmp_path_factory):
    if not _GCS_BINARY:
        pytest.skip("fake-gcs-server binary not on PATH")
    pytest.importorskip("requests")
    port = find_free_port()
    root = tmp_path_factory.mktemp("fake_gcs_data")
    proc = subprocess.Popen(
        [
            _GCS_BINARY,
            "-scheme", "http",
            "-host", "127.0.0.1",
            "-port", str(port),
            "-backend", "filesystem",
            "-filesystem-root", str(root),
        ],
        stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL,
    )
    endpoint = f"http://127.0.0.1:{port}"
    try:
        _wait_http_ready(f"{endpoint}/storage/v1/b")
        yield endpoint
    finally:
        _terminate(proc)


def _gcs_bucket(endpoint: str) -> str:
    import requests

    bucket = f"tpusnap-ci-{uuid.uuid4().hex[:8]}"
    resp = requests.post(
        f"{endpoint}/storage/v1/b", json={"name": bucket}, timeout=10
    )
    assert resp.status_code in (200, 409), resp.text
    return bucket


@pytest.mark.cloud_real
class TestRealFakeGCSServer:
    def test_plugin_round_trip(self, fake_gcs_endpoint):
        bucket = _gcs_bucket(fake_gcs_endpoint)
        _plugin_round_trip(
            f"gs://{bucket}/plugin",
            {"api_endpoint": fake_gcs_endpoint},
        )

    def test_snapshot_round_trip(self, fake_gcs_endpoint):
        bucket = _gcs_bucket(fake_gcs_endpoint)
        _snapshot_round_trip(
            f"gs://{bucket}/snap",
            {"api_endpoint": fake_gcs_endpoint},
        )


# ------------------------------------------------------------------ minio


@pytest.fixture(scope="module")
def minio_endpoint(tmp_path_factory):
    if not _MINIO_BINARY:
        pytest.skip("minio binary not on PATH")
    pytest.importorskip("aiobotocore")
    port = find_free_port()
    root = tmp_path_factory.mktemp("minio_data")
    proc = subprocess.Popen(
        [
            _MINIO_BINARY,
            "server", str(root),
            "--address", f"127.0.0.1:{port}",
            "--console-address", f"127.0.0.1:{find_free_port()}",
        ],
        env=dict(
            os.environ,
            MINIO_ROOT_USER=_MINIO_USER,
            MINIO_ROOT_PASSWORD=_MINIO_PASSWORD,
        ),
        stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL,
    )
    endpoint = f"http://127.0.0.1:{port}"
    try:
        _wait_http_ready(f"{endpoint}/minio/health/live")
        yield endpoint
    finally:
        _terminate(proc)


def _minio_options(endpoint: str):
    return {
        "client_kwargs": {
            "endpoint_url": endpoint,
            "aws_access_key_id": _MINIO_USER,
            "aws_secret_access_key": _MINIO_PASSWORD,
            "region_name": "us-east-1",
        }
    }


def _minio_bucket(endpoint: str) -> str:
    import asyncio

    from aiobotocore.session import get_session

    bucket = f"tpusnap-ci-{uuid.uuid4().hex[:8]}"

    async def create():
        session = get_session()
        async with session.create_client(
            "s3", **_minio_options(endpoint)["client_kwargs"]
        ) as client:
            await client.create_bucket(Bucket=bucket)

    asyncio.run(create())
    return bucket


@pytest.mark.cloud_real
class TestRealMinIO:
    def test_plugin_round_trip(self, minio_endpoint):
        bucket = _minio_bucket(minio_endpoint)
        _plugin_round_trip(
            f"s3://{bucket}/plugin", _minio_options(minio_endpoint)
        )

    def test_snapshot_round_trip(self, minio_endpoint):
        bucket = _minio_bucket(minio_endpoint)
        _snapshot_round_trip(
            f"s3://{bucket}/snap", _minio_options(minio_endpoint)
        )


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-v", "-m", "cloud_real"]))
