"""The sparse-expert configuration's cell off the chip: ``perf/run.py
--rehearsal`` end to end at its tiny preset (a period of 4, a window
shorter than the sequence, 8 experts routed top 2 of which 2 are held):
sound; with one term of the layer left out of the program's step (the
expert sum, the window mask); with the program's bf16 store switched on;
the reference rounded to fp8 against the limit of ``grad_diff``; and the
new per-layer readings, on hand-made observations and in the traced
rehearsal's line. CPU only; nothing here describes a TPU topology."""

import json
import os
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PERF = os.path.join(ROOT, "perf")
sys.path.insert(0, ROOT)

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
CONFIG = "smallthinker-21b-a3b"
CELL = next(w for w in MANIFEST["workloads"] if w["config"] == CONFIG)
OLD_CELL = "pythia-410m.save-loop"
NEW_METRICS = ("slab_bytes_per_state_byte", "slab_pack_ms", "blobs_per_save",
               "moe_share_of_step", "attn_share_of_step")
SEED = "2800000013"


def _perf_json(*parts):
    with open(os.path.join(PERF, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    return str(tmp_path_factory.mktemp("perf_jax_cache"))


def _run(cache_dir, *args, code=None, script="run.py"):
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=1",
        JAX_COMPILATION_CACHE_DIR=cache_dir,
    )
    cmd = [sys.executable, "-c", code] if code else [sys.executable, os.path.join(PERF, script)]
    return subprocess.run(
        [*cmd, *args], capture_output=True, text=True, timeout=400, cwd=ROOT, env=env
    )


def _cell(cache_dir, *extra, trace="0", code=None):
    proc = _run(cache_dir, "--workload", CELL["name"], "--seed", SEED, "--seconds", "1",
                "--trace", trace, "--rehearsal", *extra, code=code)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout


def _checks(stdout):
    rows = [json.loads(ln.split(": ", 1)[1]) for ln in stdout.splitlines()
            if ln.startswith("perf check: ")]
    return {r["name"]: r for r in rows}


@pytest.mark.manifest_shape
def test_the_manifest_has_the_configuration_and_its_one_cell():
    entry = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    held = _perf_json("configs", f"{CONFIG}.json")
    assert entry["source"] == held["source"] and sorted(entry["reduced"]) == sorted(held["reduced"])
    assert CELL["chips"] == 1 and CELL["traffic"] == "save_loop"
    assert [w["name"] for w in MANIFEST["workloads"] if w["config"] == CONFIG] == [CELL["name"]]
    # Every metric that the dense one-chip save loop reports, this cell reports too.
    for metric in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        cells = metric.get("workloads")
        if cells and OLD_CELL in cells:
            assert CELL["name"] in cells, metric["name"]
    new = {m["name"]: m for m in MANIFEST["per_layer"] if m["name"] in NEW_METRICS}
    assert sorted(new) == sorted(NEW_METRICS)
    # The slab readings are the write path's: every one-chip cell of a
    # ``save_loop`` mix, in the manifest's order, whatever their number.
    from test_harness import one_chip_save_loop_cells

    save_cells = one_chip_save_loop_cells()
    assert {OLD_CELL, CELL["name"]} <= set(save_cells)
    for name in NEW_METRICS[:3]:
        assert new[name]["workloads"] == save_cells
    # The step's shares are read off named scopes: this cell, and whichever
    # later one-chip training cell names its scopes so.
    cells = {w["name"]: w for w in MANIFEST["workloads"]}
    trains = next(m for m in MANIFEST["end_to_end"] if m["name"] == "train_tokens_per_s")
    for name in NEW_METRICS[3:]:
        assert CELL["name"] in new[name]["workloads"] and new[name]["layer"] == "train step"
        for listed in new[name]["workloads"]:
            assert cells[listed]["chips"] == 1 and listed in trains["workloads"], (name, listed)
    # Entries are appended: every one of the five comes after the last of PR 26's,
    # and a later PR's come after these.
    names = [m["name"] for m in MANIFEST["per_layer"]]
    assert all(names.index(n) > names.index("codec_bytes_per_state_byte") for n in NEW_METRICS)


def test_the_file_holds_the_published_widths_and_the_cut():
    """Every number of the source's config stands under its own key; the
    five keys under ``reduced`` are counts (layers, experts, heads, KV
    heads, vocabulary rows), never a width; and the reference works the
    parameters and the state out of the file to the byte."""
    from perf.reference import smallthinker

    held = _perf_json("configs", f"{CONFIG}.json")
    published = {
        "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
        "moe_ffn_hidden_size": 768, "moe_num_active_primary_experts": 6,
        "rms_norm_eps": 1e-06, "rope_theta": 1500000, "sliding_window_size": 4096,
        "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
        "tie_word_embeddings": False, "rope_scaling": None,
    }
    assert {k: held[k] for k in published} == published
    assert held["rope_layout"] == held["sliding_window_layout"] == [0, 1, 1, 1] * 13
    assert held["moe_router_outputs"] == 64 == held["published"]["moe_num_primary_experts"]
    cut = {"num_hidden_layers": (52, 4), "moe_num_primary_experts": (64, 8),
           "num_attention_heads": (28, 7), "num_key_value_heads": (4, 1),
           "vocab_size": (151936, 18992)}
    assert sorted(held["reduced"]) == sorted(cut)
    for key, (was, now) in cut.items():
        assert held["published"][key] == was and held[key] == now
    assert not [k for k in held["reduced"] if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"]
    assert smallthinker.n_params(held) == 307_632_640 == held["parameters"]
    assert smallthinker.state_bytes(held) == 3_691_591_684 == held["state_bytes"]
    assert held["assumed"]["batch"] == 1 and held["assumed"]["seq_len"] == 8192
    assert all(isinstance(held["limits"][k], float) for k in (
        "loss_gap", "grad_norm_gap", "delta_norm_gap", "grad_diff"))
    # Routed work only: a token meets 6 x 8 / 64 of an expert a layer.
    c = smallthinker.sizes(held)
    dense = dict(held, moe_num_active_primary_experts=64)
    per_expert = 6 * 3 * c["d"] * c["f"] * c["layers"]
    assert smallthinker.train_flops_per_token(dense, 8192) - smallthinker.train_flops_per_token(
        held, 8192) == pytest.approx((8 - 0.75) * per_expert)


def test_the_whole_cell_rehearses_correct_and_prints_the_new_counts(cache_dir):
    result, stdout = _cell(cache_dir, trace="1")
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] >= 1
    checks = _checks(stdout)
    for name in ("restored_bits_differ", "verify_unclean", "resumed_loss_gap", "state_bytes_off",
                 "compile_events_in_window", "tpusnap_warnings"):
        assert checks[name]["value"] == 0, name
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    # At the tiny size every leaf is under the slab threshold: one slab, one blob.
    assert metrics["slab_bytes_per_state_byte"] == 1.0
    assert metrics["blobs_per_save"] == 1 and metrics["pack_fallbacks"] == 0
    assert metrics["dtoh_bytes_per_state_byte"] == 2.0
    assert "slab_pack_ms" in metrics and metrics["slab_pack_ms"] is None  # a time: never from the CPU
    # No device plane on the CPU: the scopes' shares have nothing to read.
    assert "moe_share_of_step" not in metrics and "attn_share_of_step" not in metrics


LEFT_OUT = """
import sys
sys.path.insert(0, {root!r})
import jax.numpy as jnp
from tpusnap.models import smallthinker
{patch}
sys.argv = ["perf/run.py"] + sys.argv[1:]
from perf import run
sys.exit(run.main())
"""
NO_EXPERTS = "smallthinker.SmallThinker.experts = lambda self, lp, a, x: jnp.zeros_like(x)"
NO_WINDOW = """
sound = smallthinker.blocked_attention
smallthinker.blocked_attention = lambda q, k, v, *, window, q_block: sound(
    q, k, v, window=None, q_block=q_block)
"""


@pytest.mark.parametrize("patch", [NO_EXPERTS, NO_WINDOW], ids=["expert_sum", "window_mask"])
def test_a_term_of_the_layer_left_out_of_the_step_is_not_correct(cache_dir, patch):
    result, stdout = _cell(cache_dir, code=LEFT_OUT.format(root=ROOT, patch=patch))
    assert result["correct"] is False
    failed = [name for name, row in _checks(stdout).items() if not row["ok"]]
    assert "grad_diff" in failed and "restored_bits_differ" not in failed, failed


def test_storing_the_state_in_bf16_is_not_correct(cache_dir):
    result, stdout = _cell(cache_dir, "--control", "store_bf16")
    assert result["correct"] is False
    assert '"name": "restored_bits_differ", "ok": false' in stdout


def test_fp8_arithmetic_reads_over_the_limit_of_grad_diff(cache_dir):
    """The reference with its linear layers and expert products rounded to
    fp8 reads over the preset's limit on every seed; the bf16 program reads
    under it."""
    limit = _perf_json("configs", _perf_json("configs", f"{CONFIG}.json")["rehearsal_config"]
                       + ".json")["limits"]["grad_diff"]
    proc = _run(cache_dir, "--config", CONFIG, "--seeds", "3", "--first-seed", SEED,
                "--controls", "fp8", "--rehearsal", script="readings.py")
    assert proc.returncode == 0, proc.stderr[-3000:]
    rows = [json.loads(ln.split(": ", 1)[1]) for ln in proc.stdout.splitlines()
            if ln.startswith("perf reading:")]
    assert len(rows) == 3
    for row in rows:
        assert row["sound"]["grad_diff"] <= limit < row["control_fp8"]["grad_diff"], row


# ---- the new readings on hand-made observations


def _reading(name, obs):
    from perf import harness

    spec = harness.layer_metric_spec(name)
    return harness.load_module("reducers", spec["reducer"]).reduce(obs, **spec.get("args", {}))


def test_slab_bytes_blobs_and_pack_time_per_save():
    """Two saves of a 1000-byte state: 600 bytes in two slabs and five
    blobs each; the pack takes 0.1 s a slab."""
    ops = [{"t_call": 0.0, "t_done": 10.0}, {"t_call": 10.0, "t_done": 20.0}]
    counters, spans = [], []
    for t0 in (0.0, 10.0):
        for i, nbytes in enumerate((400, 200)):
            counters += [{"name": "batcher.device_slab_bytes", "t": t0 + 1 + i, "delta": nbytes},
                         {"name": "batcher.device_slabs", "t": t0 + 1 + i, "delta": 1}]
            spans.append({"name": "slab.pack", "start": t0 + 1 + i, "end": t0 + 1.1 + i,
                          "bytes": nbytes, "kind": "work"})
        counters += [{"name": "storage.writes", "t": t0 + 5 + i, "delta": 1} for i in range(5)]
    obs = {"ops": ops, "counters": counters, "spans": spans, "state_bytes": 1000}
    assert _reading("slab_bytes_per_state_byte", obs) == pytest.approx(0.6)
    assert _reading("slab_pack_ms", obs) == pytest.approx(200.0)
    assert _reading("blobs_per_save", obs) == 5
    # A program without the counter or the span (this change's parent): nothing to read.
    old = {**obs, "counters": [c for c in counters if c["name"] == "storage.writes"], "spans": [
        {"name": "dtoh", "start": 1.0, "end": 2.0, "bytes": 600, "kind": "work"}]}
    assert _reading("slab_bytes_per_state_byte", old) is None
    assert _reading("slab_pack_ms", old) is None
    assert _reading("blobs_per_save", old) == 5


def _event(name, start, end):
    return types.SimpleNamespace(name=name, start_ns=int(start * 1e9),
                                 duration_ns=int((end - start) * 1e9))


def test_the_scopes_share_of_busy_time_on_a_synthetic_line():
    """Ten busy seconds: a conditional of 4 s whose body holds a grouped
    product (2 s, named by the compiler, not by the scope) and a scatter
    under ``moe.experts`` (1 s); an attention fusion (3 s); a projection
    under no scope (3 s). The scopes are in the framework names, which the
    profiler keeps beside the HLO lines."""
    from perf.reducers import trace_scope_share as tss

    events = [
        _event("%conditional.1 = f32[8] conditional(...)", 0.0, 4.0),
        _event("%ragged-dot-none.7 = bf16[8,8] custom-call(...)", 0.5, 2.5),
        _event("%scatter.3 = f32[8] scatter(...)", 2.5, 3.5),
        _event("%fusion.9 = f32[8] fusion(...)", 4.0, 7.0),
        _event("%fusion.10 = f32[8] fusion(...)", 7.0, 10.0),
    ]
    names = {
        events[0].name: "jit(step)/jvp(moe.experts)/cond:",
        events[1].name: "ragged-dot-none:",
        events[2].name: "jit(step)/transpose(jvp(moe.experts))/cond/branch_1_fun/scatter-add:",
        events[3].name: "jit(step)/jvp(attn.window)/checkpoint/dot_general:",
        events[4].name: "jit(step)/smoe.x/dot_general:",
    }
    inside, busy, matched = tss.scope_seconds(events, tss.matcher("moe.", ["ragged-dot"], names))
    # The conditional's own second (4 s less its body's 3 s) is under the scope too.
    assert busy == pytest.approx(10.0) and inside == pytest.approx(4.0)
    assert matched == {"kernel": 1, "scope": 2}
    inside, busy, matched = tss.scope_seconds(events, tss.matcher("attn.", (), names))
    assert inside == pytest.approx(3.0) and matched == {"scope": 1}
    assert tss.scope_seconds(events, tss.matcher("nothing.", (), names))[0] == 0.0
    # No trace (an untraced or a CPU run): nothing to read.
    assert tss.reduce({"trace": None}, scope="moe.") is None


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number, value):
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    value = value.encode() if isinstance(value, str) else value
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def test_framework_names_are_read_off_the_files_own_protobuf(tmp_path):
    """An ``XSpace`` written by hand, field by field: a host plane, device
    1 and device 0, whose two operations carry their framework names as
    the ``tf_op`` stat of their metadata, one as a string and one as a
    reference to a stat's name."""
    from perf.reducers import trace_scope_share as tss

    def stat_meta(key, name):
        return _field(5, _field(1, key) + _field(2, _field(1, key) + _field(2, name)))

    def event_meta(key, name, stat):
        return _field(4, _field(1, key) + _field(2, _field(1, key) + _field(2, name)
                                                 + _field(5, stat) + _field(4, "display")))

    device0 = (_field(1, 7) + _field(2, "/device:TPU:0") + _field(3, b"\x0a\x00")
               + stat_meta(300, "tf_op") + stat_meta(301, "jit(step)/attn.global/dot_general:")
               + stat_meta(2, "flops")
               + event_meta(1, "%fusion.1 = f32[8] fusion()",
                            _field(1, 300) + _field(5, "jit(step)/jvp(moe.route)/top_k:"))
               + event_meta(2, "%fusion.2 = f32[8] fusion()", _field(1, 300) + _field(7, 301))
               + event_meta(3, "%copy.3 = f32[8] copy()", _field(1, 2) + _field(3, 99)))
    device1 = _field(2, "/device:TPU:1") + stat_meta(1, "tf_op") + event_meta(
        1, "%other = f32[8] copy()", _field(1, 1) + _field(5, "jit(step)/moe.experts/x:"))
    space = (_field(1, _field(2, "/host:CPU")) + _field(1, device1) + _field(1, device0)
             + _field(4, "a warning"))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space)
    assert tss.framework_names(str(path)) == {
        "%fusion.1 = f32[8] fusion()": "jit(step)/jvp(moe.route)/top_k:",
        "%fusion.2 = f32[8] fusion()": "jit(step)/attn.global/dot_general:",
    }
    (tmp_path / "h.xplane.pb").write_bytes(_field(1, _field(2, "/host:CPU")))
    assert tss.framework_names(str(tmp_path / "h.xplane.pb")) == {}
