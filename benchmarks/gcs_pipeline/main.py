"""GCS-plugin full-pipeline benchmark against the fake server, with
injected per-request latency.

The north-star production target is GCS (BASELINE.md; the reference
publishes network-storage rows next to local FS,
/root/reference/benchmarks/ddp/README.md:21-24). Real-bucket CI needs
credentials this environment does not have, so this harness measures
the part of cloud throughput the FRAMEWORK controls — how many
requests the pipeline keeps in flight — against the same fake GCS
server the fault-matrix tests use (tests/test_gcs.py), with a fixed
latency injected into EVERY request (simulating cloud RTT; loopback
bandwidth is effectively infinite, so latency-hiding is the whole
game, exactly as it is against a real bucket from a TPU VM).

Reported per phase (take / restore):

- wall seconds and effective GB/s through the FULL pipeline
  (Snapshot.take / restore with slab batching, resumable-upload
  chunking, ranged downloads);
- requests issued and the serial floor (requests x latency): what a
  one-request-at-a-time client would need for latency alone;
- concurrency = serial floor / wall — the latency-hiding factor the
  scheduler + plugin achieve end to end.

Run:
    JAX_PLATFORMS=cpu python benchmarks/gcs_pipeline/main.py \
        [--latency-ms 30] [--total-mb 256]
"""

import argparse
import os
import sys
import threading
import time

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, _REPO)
sys.path.insert(0, os.path.join(_REPO, "tests"))


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--latency-ms", type=float, default=100.0)
    parser.add_argument("--total-mb", type=int, default=256)
    parser.add_argument(
        "--upload-chunk-mb",
        type=int,
        default=8,
        help="resumable-upload chunk size (production default is 100 MB; "
        "smaller here so a modest state still exercises multi-chunk "
        "sessions)",
    )
    args = parser.parse_args()

    from http.server import ThreadingHTTPServer

    import numpy as np

    import tpusnap.storage_plugins.gcs as gcs_mod
    from test_gcs import FakeGCS, _make_handler  # the fault-matrix fake
    from tpusnap import PytreeState, Snapshot
    from tpusnap.knobs import override_slab_size_threshold_bytes

    state_srv = FakeGCS()
    server = ThreadingHTTPServer(("127.0.0.1", 0), _make_handler(state_srv))
    threading.Thread(target=server.serve_forever, daemon=True).start()
    endpoint = f"http://127.0.0.1:{server.server_address[1]}"

    chunk = args.upload_chunk_mb << 20
    prev_up, prev_down = gcs_mod._UPLOAD_CHUNK_SIZE, gcs_mod._DOWNLOAD_CHUNK_SIZE
    gcs_mod._UPLOAD_CHUNK_SIZE = chunk
    gcs_mod._DOWNLOAD_CHUNK_SIZE = chunk

    total = args.total_mb << 20
    rng = np.random.default_rng(0)
    # Mixed shape census like a real train state: a few large arrays
    # (multi-chunk resumable sessions) + many small ones (slab-batched
    # into a handful of uploads — the reason cloud stores need slabs).
    big = {
        f"big{i}": rng.integers(0, 255, total // 8, dtype=np.uint8)
        for i in range(6)
    }
    small = {
        f"small{i}": rng.integers(0, 255, 64 << 10, dtype=np.uint8)
        for i in range(64)
    }
    state = {**big, **small}
    nbytes = sum(a.nbytes for a in state.values())
    opts = {"api_endpoint": endpoint, "deadline_sec": 120.0}
    lat = args.latency_ms / 1e3

    def phase(name, fn):
        state_srv.request_log.clear()
        t0 = time.perf_counter()
        fn()
        wall = time.perf_counter() - t0
        reqs = len(state_srv.request_log)
        serial_floor = reqs * lat
        print(
            f"{name:8s} {wall:6.2f}s  {nbytes / wall / 1e9:5.2f} GB/s  "
            f"{reqs:4d} requests, serial latency floor "
            f"{serial_floor:6.2f}s -> concurrency {serial_floor / wall:4.1f}x"
        )
        return wall

    # The whole harness is a ~1/16-scale model of the production cloud
    # shape census: upload chunks 8 MB (prod 100 MB), slab threshold
    # 2 MB (prod 128 MB) — so the large arrays are standalone objects
    # whose resumable sessions upload IN PARALLEL (chunks within one
    # session are protocol-sequential), and the small arrays still
    # batch into a handful of slab objects.
    try:
        print(
            f"state: {nbytes / 1e6:.0f} MB ({len(big)} large + {len(small)} "
            f"small arrays), latency {args.latency_ms:.0f} ms/request, "
            f"upload/download chunk {args.upload_chunk_mb} MB"
        )
        state_srv.latency_s = lat
        with override_slab_size_threshold_bytes(2 << 20):
            phase(
                "take",
                lambda: Snapshot.take(
                    "gs://bkt/snap",
                    {"m": PytreeState(state)},
                    storage_options=opts,
                ),
            )

            target = {
                "m": PytreeState(
                    {k: np.zeros_like(v) for k, v in state.items()}
                )
            }
            phase(
                "restore",
                lambda: Snapshot(
                    "gs://bkt/snap", storage_options=opts
                ).restore(target),
            )
        ok = all(
            np.array_equal(target["m"].tree[k], v) for k, v in state.items()
        )
        print(f"restore verified: {ok}")
        if not ok:
            raise SystemExit(1)
    finally:
        gcs_mod._UPLOAD_CHUNK_SIZE = prev_up
        gcs_mod._DOWNLOAD_CHUNK_SIZE = prev_down
        server.shutdown()


if __name__ == "__main__":
    main()
