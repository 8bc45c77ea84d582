"""Continuous delta checkpointing (``tpusnap.delta``): DeltaStream
micro-commits, chain resolution, compaction, retention pinning.

Covers: an unchanged model streams ~zero payload bytes per micro-commit
(dual-hash skip asserted via the stream's byte accounting AND the
member's on-disk payload files); restore of a delta head replays base +
committed chain bit-identically (flat lookups at any depth); cadence
free-running and step-gated capture (step-gated heads land EXACTLY on a
mark_step boundary state); chain compaction via materialize bounds the
chain and retires superseded members; resolve_chain names head / torn
tail / debris; retention never reclaims a member a kept head references
(transitive pinning); the SLO tracker is anchored by micro-commits.
SIGKILL crash windows live in test_crash_matrix.py.
"""

import os

import numpy as np
import pytest

from tpusnap import (
    DeltaStream,
    Snapshot,
    StateDict,
    resolve_chain,
    verify_snapshot,
)
from tpusnap.delta import delta_fields, delta_payload_bytes, member_name
from tpusnap.inspect import load_snapshot_metadata


def _payload_files(root: str):
    """PAYLOAD files under a snapshot dir (excluding metadata and the
    .tpusnap sidecars)."""
    out = []
    for dirpath, _, files in os.walk(root):
        if ".tpusnap" in dirpath.split(os.sep):
            continue
        for f in files:
            if f != ".snapshot_metadata":
                out.append(os.path.relpath(os.path.join(dirpath, f), root))
    return sorted(out)


def _state(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "app": StateDict(
            w=rng.standard_normal((256, 64)).astype(np.float32),
            b=rng.standard_normal((128,)).astype(np.float32),
        )
    }


def test_stream_commit_restore_bit_identical(tmp_path):
    root = str(tmp_path / "stream")
    state = _state()
    with Snapshot.stream(root, state, cadence_s=3600) as s:
        assert s.seq == 0
        assert s.head.endswith(member_name(0))
        state["app"]["w"][0, :] = 42.0
        snap = s.commit_now()
        assert s.seq == 1
        # The committed member is a real snapshot: verifies clean and
        # carries its chain fields.
        assert verify_snapshot(snap.path).clean
        d = delta_fields(snap.metadata)
        assert d is not None and d["seq"] == 1
        assert d["parent"] == member_name(0)
        state["app"]["b"][:] = -1.0
        s.commit_now()
        expected_w = state["app"]["w"].copy()
        expected_b = state["app"]["b"].copy()
    # close() ran a final (unchanged) commit; head replays the chain.
    rep = resolve_chain(root)
    assert rep.head is not None
    target = {
        "app": StateDict(
            w=np.zeros((256, 64), np.float32), b=np.zeros(128, np.float32)
        )
    }
    Snapshot(rep.head_path).restore(target)
    assert np.array_equal(target["app"]["w"], expected_w)
    assert np.array_equal(target["app"]["b"], expected_b)
    # Intermediate members restore too (any member is a snapshot).
    mid = os.path.join(root, member_name(1))
    out = Snapshot(mid).read_object("0/app/w")
    assert np.array_equal(out[0], np.full(64, 42.0, np.float32))


def test_unchanged_model_streams_zero_payload_bytes(tmp_path):
    from tpusnap import telemetry

    root = str(tmp_path / "stream")
    state = _state(1)
    s = Snapshot.stream(root, state, cadence_s=3600)
    commits_before = telemetry.counter_value("delta.commits")
    snap = s.commit_now()
    # Dual-hash skip: nothing changed since the base — the member holds
    # NO payload files and the stream accounts zero bytes written.
    assert s.stats["last_commit_bytes"] == 0
    assert _payload_files(snap.path) == []
    assert delta_payload_bytes(snap.metadata) == 0
    assert telemetry.counter_value("delta.commits") == commits_before + 1
    # ... and still restores the full state through the base references.
    assert verify_snapshot(snap.path).clean
    target = {
        "app": StateDict(
            w=np.zeros((256, 64), np.float32), b=np.zeros(128, np.float32)
        )
    }
    Snapshot(snap.path).restore(target)
    assert np.array_equal(target["app"]["w"], state["app"]["w"])
    # A changed leaf rewrites only itself (b is slab-batched, so the
    # new slab holds just the one changed member: b's 512 bytes).
    state["app"]["b"][0] = 123.0
    snap2 = s.commit_now()
    files = _payload_files(snap2.path)
    assert len(files) == 1, files
    assert s.stats["last_commit_bytes"] == state["app"]["b"].nbytes
    s.close(final_commit=False)


def test_cadence_free_running_commits(tmp_path):
    import time

    root = str(tmp_path / "stream")
    state = _state(2)
    s = Snapshot.stream(root, state, cadence_s=0.3)
    t0 = time.monotonic()
    while time.monotonic() - t0 < 1.4:
        state["app"]["w"][0, 0] += 1.0
        time.sleep(0.02)
    s.close(final_commit=False)
    s.raise_if_failed()
    # ~4 intervals elapsed; allow generous slack for slow CI hosts.
    assert s.stats["commits"] >= 2, s.stats
    assert s.seq >= 2


def test_mark_step_gated_capture_lands_on_step_boundaries(tmp_path):
    """With mark_step gating, every committed increment must equal a
    state AS OF some step boundary — never a mid-mutation mixture."""
    import time

    root = str(tmp_path / "stream")
    state = _state(3)
    boundary_states = []

    def snapshot_boundary():
        boundary_states.append(
            (state["app"]["w"].copy(), state["app"]["b"].copy())
        )

    snapshot_boundary()  # the base capture in __init__ sees this state
    s = Snapshot.stream(root, state, cadence_s=0.25)
    t0 = time.monotonic()
    while time.monotonic() - t0 < 1.3:
        # "training step": in-place mutation between boundaries.
        state["app"]["w"] += 1.0
        state["app"]["b"] -= 0.5
        snapshot_boundary()
        s.mark_step(bytes_changed=state["app"]["w"].nbytes)
        time.sleep(0.02)
    s.close(final_commit=False)
    s.raise_if_failed()
    assert s.stats["commits"] >= 2, s.stats
    assert s.stats["steps_marked"] > 0
    rep = resolve_chain(root)
    target = {
        "app": StateDict(
            w=np.zeros((256, 64), np.float32), b=np.zeros(128, np.float32)
        )
    }
    Snapshot(rep.head_path).restore(target)
    matches = [
        i
        for i, (w, b) in enumerate(boundary_states)
        if np.array_equal(target["app"]["w"], w)
        and np.array_equal(target["app"]["b"], b)
    ]
    assert matches, "head is not any step-boundary state (torn capture)"


def test_compaction_bounds_chain_and_retires_members(tmp_path):
    root = str(tmp_path / "stream")
    state = _state(4)
    s = Snapshot.stream(root, state, cadence_s=3600, max_chain=2)
    for i in range(5):
        state["app"]["w"][i, :] = float(i)
        s.commit_now()
    expected = state["app"]["w"].copy()
    s.close(final_commit=False)
    assert s.stats["compactions"] >= 1, s.stats
    assert len(s.chain) <= 2, s.chain
    rep = resolve_chain(root)
    # Superseded members were retired from disk (local fs).
    on_disk = {m.name for m in rep.members}
    assert set(s.chain) <= on_disk
    assert len(on_disk) <= 3, on_disk  # chain + at most the fresh head
    # The compacted base is self-contained and the head restores.
    target = {
        "app": StateDict(
            w=np.zeros((256, 64), np.float32), b=np.zeros(128, np.float32)
        )
    }
    Snapshot(rep.head_path).restore(target)
    assert np.array_equal(target["app"]["w"], expected)
    assert verify_snapshot(rep.head_path).clean


def test_resolve_chain_names_torn_tail_and_debris(tmp_path):
    import json

    root = str(tmp_path / "stream")
    state = _state(5)
    s = Snapshot.stream(root, state, cadence_s=3600)
    state["app"]["w"][0, 0] = 9.0
    s.commit_now()
    s.close(final_commit=False)
    # Manufacture a torn tail: journal marker with stream fields, no
    # metadata — exactly what a SIGKILLed micro-commit leaves.
    torn = tmp_path / "stream" / member_name(2) / ".tpusnap"
    torn.mkdir(parents=True)
    (torn / "journal").write_text(
        json.dumps(
            {
                "take_id": "deadbeef",
                "world_size": 1,
                "started_at": 0.0,
                "incremental_from": "../" + member_name(1),
                "stream": {
                    "stream": s.stream_id,
                    "seq": 2,
                    "parent": member_name(1),
                },
            }
        )
    )
    # ... and a debris dir (half-retired compaction leftover).
    junk = tmp_path / "stream" / "delta-000090"
    junk.mkdir()
    (junk / "leftover.blob").write_bytes(b"x" * 32)
    rep = resolve_chain(root)
    assert rep.torn_tail == member_name(2)
    assert rep.head == member_name(1)  # recovery ignores the torn tail
    assert "delta-000090" in rep.debris
    # fsck of the torn member classifies it and names the delta state.
    from tpusnap.lifecycle import fsck_snapshot

    fr = fsck_snapshot(str(tmp_path / "stream" / member_name(2)))
    assert fr.state == "torn"
    assert fr.delta and fr.delta["seq"] == 2
    assert "torn delta micro-commit seq 2" in fr.summary()
    # Root-level fsck exits 4 on the torn tail; info renders the chain.
    from tpusnap.__main__ import main

    assert main(["fsck", root]) == 4
    assert main(["info", root]) == 0


def test_retention_pins_chain_of_kept_head(tmp_path):
    """`retain --keep 1` on a stream root: the kept head references
    earlier members (unchanged blobs dedup into them) — retention must
    materialize it BEFORE deleting them, never leaving a dangling
    chain."""
    from tpusnap.retention import _referenced_bases, apply_retention

    root = str(tmp_path / "stream")
    state = _state(6)
    s = Snapshot.stream(root, state, cadence_s=3600, max_chain=100)
    # b never changes -> every increment references the base's b blob.
    state["app"]["w"][0, :] = 1.0
    s.commit_now()
    state["app"]["w"][1, :] = 2.0
    s.commit_now()
    expected_w = state["app"]["w"].copy()
    expected_b = state["app"]["b"].copy()
    s.close(final_commit=False)
    head = os.path.join(root, member_name(2))
    bases = _referenced_bases(head)
    assert any(member_name(0) in b for b in bases), bases
    plan = apply_retention(root, keep_last=1)
    assert plan.keep == [os.path.abspath(head)]
    assert plan.materialize == [os.path.abspath(head)], (
        "kept head referencing doomed chain members must be materialized"
    )
    assert not os.path.exists(os.path.join(root, member_name(0)))
    target = {
        "app": StateDict(
            w=np.zeros((256, 64), np.float32), b=np.zeros(128, np.float32)
        )
    }
    Snapshot(head).restore(target)
    assert np.array_equal(target["app"]["w"], expected_w)
    assert np.array_equal(target["app"]["b"], expected_b)
    assert verify_snapshot(head).clean


def test_referenced_bases_walks_transitively(tmp_path):
    """Defense in depth: a hand-built NON-collapsed chain (C→B→A where
    C's metadata only names B) must still pin A through the transitive
    walk."""
    from tpusnap.retention import _referenced_bases

    a, b, c = (str(tmp_path / n) for n in ("a", "b", "c"))
    st = _state(7)
    Snapshot.take(a, st)
    st["app"]["w"][0, 0] += 1  # w rewrites in b; bias still refs a
    Snapshot.take(b, st, incremental_from=a)
    # Nothing changes: c references b's w AND (collapsed) a's bias.
    Snapshot.take(c, st, incremental_from=b)
    direct = _referenced_bases(c)
    assert os.path.abspath(a) in direct and os.path.abspath(b) in direct
    # b itself references only a; the transitive walk from c reaches a
    # even through b (defense in depth for non-collapsed chains).
    assert _referenced_bases(b) == [os.path.abspath(a)]


def test_stream_anchors_slo_tracker(tmp_path):
    from tpusnap import slo

    slo.reset_tracker()
    root = str(tmp_path / "stream")
    state = _state(8)
    s = Snapshot.stream(root, state, cadence_s=0.5)
    st = slo.tracker().snapshot_state()
    assert st["stream_cadence_s"] == 0.5
    # The base commit anchored the RPO clock seconds ago, not minutes.
    assert st["rpo_s"] < 60.0
    state["app"]["w"][0, 0] = 7.0
    s.commit_now()
    st = slo.tracker().snapshot_state()
    assert st["last_commit_take_id"], st
    assert st["commit_interval_s"] is not None
    s.close(final_commit=False)
    st = slo.tracker().snapshot_state()
    assert st["stream_cadence_s"] is None


def test_closed_stream_leaves_slo_check_green(tmp_path):
    """With the RPO objective armed from the knob, a stream of three
    micro-commits that closes cleanly leaves records `slo --check`
    passes, and its own stats carry the widest commit interval."""
    from tpusnap import slo
    from tpusnap.__main__ import main
    from tpusnap.knobs import override_slo_thresholds, override_telemetry_dir

    slo.reset_tracker()
    state = _state(9)
    with override_telemetry_dir(str(tmp_path / "tele")), override_slo_thresholds(
        rpo_s=3600.0
    ):
        s = Snapshot.stream(str(tmp_path / "stream"), state, cadence_s=3600.0)
        for k in range(3):
            state["app"]["w"][k, :] = float(k + 1)
            s.commit_now()
        s.close(final_commit=False)
        s.raise_if_failed()
        assert s.stats["commits"] >= 3, s.stats
        assert s.stats["max_commit_interval_s"] is not None
        assert main(["slo", "--check"]) == 0
    slo.reset_tracker()


def test_stream_multiprocess_needs_coordination(tmp_path):
    """A multi-process stream runs its elastic control plane over the
    jax.distributed coordination KV — opening one without the service
    must fail loudly, not wedge."""
    from tpusnap.comm import Communicator

    class FakeMulti(Communicator):
        @property
        def world_size(self):
            return 2

    with pytest.raises(RuntimeError, match="jax.distributed"):
        Snapshot.stream(
            str(tmp_path / "s"), _state(), comm=FakeMulti()
        )


def test_stream_resumes_committed_chain(tmp_path):
    """Reopening a stream root RESUMES the committed chain across
    process lifetimes: the new stream adopts the head's stream id and
    sequence, takes no new base, and its first micro-commit extends
    the existing chain."""
    root = str(tmp_path / "stream")
    state = _state(11)
    s = Snapshot.stream(root, state, cadence_s=3600)
    sid = s.stream_id
    state["app"]["w"][0, 0] = 1.0
    s.commit_now()
    s.close(final_commit=False)

    s2 = Snapshot.stream(root, state, cadence_s=3600)
    try:
        assert s2.stream_id == sid
        assert s2.seq == 1  # adopted, not reset
        state["app"]["w"][0, 1] = 2.0
        snap = s2.commit_now()
        assert s2.seq == 2
        # No second base: the resumed commit extends the old chain.
        assert not os.path.isdir(
            os.path.join(root, "base-000001")
        )
        restored = _state()
        snap.restore(restored)
        np.testing.assert_array_equal(
            restored["app"]["w"], state["app"]["w"]
        )
        rep = resolve_chain(root)
        assert rep.head == member_name(2)
        assert member_name(0) in rep.chain
        assert verify_snapshot(rep.head_path).clean
    finally:
        s2.close(final_commit=False)


def test_stream_refuses_foreign_root(tmp_path):
    """A root holding committed NON-stream snapshots still refuses: a
    fresh base under foreign snapshot dirs would silently change what
    the directory means."""
    root = str(tmp_path / "root")
    Snapshot.take(os.path.join(root, "plain"), _state(11))
    with pytest.raises(ValueError, match="non-stream"):
        Snapshot.stream(root, _state(11), cadence_s=3600)


def test_stream_rejects_nonpositive_cadence(tmp_path):
    with pytest.raises(ValueError, match="cadence_s"):
        Snapshot.stream(str(tmp_path / "s"), _state(12), cadence_s=0)
    with pytest.raises(ValueError, match="cadence_s"):
        Snapshot.stream(str(tmp_path / "s"), _state(12), cadence_s=-1.5)


def test_failed_stream_clears_slo_cadence(tmp_path):
    """A stream stopped by a FAILED micro-commit must clear the SLO
    cadence gauge — a dashboard must not read 'delta stream active'
    while the stream is dead and exposure grows."""
    import shutil

    from tpusnap import slo

    slo.reset_tracker()
    root = str(tmp_path / "stream")
    state = _state(13)
    s = Snapshot.stream(root, state, cadence_s=3600)
    assert slo.tracker().snapshot_state()["stream_cadence_s"] == 3600
    # Sabotage the chain: the next increment's dedup base is gone.
    shutil.rmtree(os.path.join(root, member_name(0)))
    state["app"]["w"][0, 0] = 1.0
    with pytest.raises(Exception):
        s.commit_now()
    # commit_now propagates to the caller and keeps the stream open;
    # a WORKER/mark_step failure stops the stream and must clear the
    # gauge — simulate via the failure path directly.
    s._fail(RuntimeError("boom"), where="test")
    assert slo.tracker().snapshot_state()["stream_cadence_s"] is None
    with pytest.raises(RuntimeError, match="recovery point"):
        s.raise_if_failed()
    s.close(final_commit=False)  # idempotent on a failed stream


def test_commit_after_close_raises(tmp_path):
    s = Snapshot.stream(str(tmp_path / "s"), _state(9), cadence_s=3600)
    s.close(final_commit=False)
    with pytest.raises(RuntimeError, match="closed"):
        s.commit_now()
    # close is idempotent.
    assert s.close() is not None


def test_chain_lookups_stay_flat(tmp_path):
    """Writer-side collapse: every external location of a deep head
    points DIRECTLY at the member holding the bytes (one '../' hop) —
    lookups never chase intermediate members."""
    root = str(tmp_path / "stream")
    state = _state(10)
    s = Snapshot.stream(root, state, cadence_s=3600, max_chain=100)
    for i in range(4):
        state["app"]["w"][i, :] = float(i + 1)
        s.commit_now()
    s.close(final_commit=False)
    md = load_snapshot_metadata(os.path.join(root, member_name(4)))
    from tpusnap.inspect import iter_blobs
    from tpusnap.manifest_ops import external_reference_depth

    # The chain-resolution invariant: at any chain depth, every lookup
    # is ONE parent hop ("../<member>/<path>"), never a chase through
    # intermediates.
    assert external_reference_depth(md.manifest) <= 1
    for blob in iter_blobs(md.manifest):
        if blob.location.startswith("../"):
            member = blob.location.split("/")[1]
            assert os.path.isdir(os.path.join(root, member)), blob.location
