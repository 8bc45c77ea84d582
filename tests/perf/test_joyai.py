"""The latent-attention, sparse-expert configuration's cell off the chip: the
manifest's entries and the configuration file (the published keys, the four
cuts, the state's bytes against what the mix asks of the storage);
``perf/run.py --rehearsal`` end to end at its tiny preset (a dense layer, two
expert layers of 16 routed top 4 with 4 held, the multi-token-prediction
module): sound; with the shared expert, the module's term or the routed
scale left out of the program's step; with the program's bf16 store switched
on; the reference rounded to fp8 against the limit of ``grad_diff``; and the
three readings of the step's scopes, on a hand-made line and in the compiled
step's own names. CPU only; nothing here describes a TPU topology.

No test here carries the marker ``manifest_shape``, though the first three
read the manifest alone: ``tests/perf/test_manifest_grows.py`` lists the
files whose marked tests it runs over a grown copy, and a ``model_config`` PR
edits no file under ``tests/perf/`` that exists (PERF.md 7, "Open after PR
40" (1): the same as ``test_ouro.py``)."""

import json
import os
import sys

import pytest
# The looped cell's tests drive the same command the same way: its helpers
# (a run of ``perf/`` on the CPU, the ``perf check:`` rows, a hand-made event).
from test_ouro import _checks, _event, _perf_json, _run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PERF = os.path.join(ROOT, "perf")
sys.path.insert(0, ROOT)

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
CONFIG = "joyai-llm-flash"
PRESET = "tiny-joyai"
CELL = next(w for w in MANIFEST["workloads"] if w["config"] == CONFIG)
TWIN = "ouro-2.6b.save-loop-donated"  # the same mix under another donating step
SPARSE = "smallthinker-21b-a3b.save-loop"  # the other expert layer, the other odd minor dimension
NEW_METRICS = {"latent_share_of_step": "latent.", "shared_expert_share_of_step": "shared.",
               "mtp_share_of_step": "mtp."}
TWINS_OWN = {"loop_share_of_step", "exit_share_of_step"}
SEED = "4600000013"


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    return str(tmp_path_factory.mktemp("perf_jax_cache"))


def _cell(cache_dir, *extra, trace="0", code=None):
    proc = _run(cache_dir, "--workload", CELL["name"], "--seed", SEED, "--seconds", "1.7",
                "--trace", trace, "--rehearsal", *extra, code=code)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout


def test_the_manifest_has_the_configuration_and_its_one_cell():
    entry = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    held = _perf_json("configs", f"{CONFIG}.json")
    assert entry["source"] == held["source"] and entry["file"] == f"perf/configs/{CONFIG}.json"
    assert entry["reduced"] == list(held["reduced"]) == [
        "num_hidden_layers", "n_routed_experts", "num_attention_heads", "num_key_value_heads",
        "vocab_size"]
    assert (CELL["name"], CELL["traffic"], CELL["chips"]) == (
        f"{CONFIG}.save-loop-donated", "save_loop_20s", 1)
    assert [w["name"] for w in MANIFEST["workloads"] if w["config"] == CONFIG] == [CELL["name"]]
    assert "256 tokens" in CELL["why"] and "attention" in CELL["why"]  # who sees more than its share
    # Wherever the looped cell of the same mix is listed, end to end and per
    # layer (``train_tokens_per_s`` and ``staged_wait_ms`` among them), this
    # cell is listed after it, but for the looped step's own two scopes.
    shared = [m for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]
              if TWIN in m.get("workloads", ()) and m["name"] not in TWINS_OWN]
    assert {"train_tokens_per_s", "save_stall_ms", "staged_wait_ms", "hbm_peak_share",
            "attn_share_of_step", "slab_bytes_per_state_byte", "slab_pack_ms", "blobs_per_save",
            "save_durable_s.one_chip"} <= {m["name"] for m in shared}
    for metric in shared:
        cells = metric["workloads"]
        assert CELL["name"] in cells and cells.index(CELL["name"]) > cells.index(TWIN), metric
    per_layer = {m["name"]: m for m in MANIFEST["per_layer"]}
    # The expert layer's share, and the turn on the host of a leaf whose minor
    # dimension is no multiple of 128 (the head's 16,160 columns), as the
    # sparse cell has them.
    sparse_own = ("moe_share_of_step", "relayout_ms", "relayout_bytes_per_state_byte")
    for name in sparse_own:
        cells = per_layer[name]["workloads"]
        assert cells.index(CELL["name"]) > cells.index(SPARSE), name
    for absent in TWINS_OWN:
        assert CELL["name"] not in per_layer[absent]["workloads"], absent
    # Its own three readings: data for the reducer that reads a named scope's share.
    names = [m["name"] for m in MANIFEST["per_layer"]]
    for name, scope in NEW_METRICS.items():
        assert per_layer[name] == {
            "name": name, "unit": "%", "better": "lower", "source": "device_trace",
            "layer": "train step", "moves": "train_tokens_per_s", "workloads": [CELL["name"]]}
        assert names.index(name) > names.index("exit_share_of_step")  # appended
        spec = _perf_json("layer_metrics", f"{name}.json")
        assert spec["reducer"] == "trace_scope_share" and spec["args"] == {"scope": scope}
        assert not spec.get("count")  # a share of device time: never printed from the CPU
    # Listed wherever this cell is listed: nothing else of the manifest names it.
    listed = {m["name"] for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]
              if CELL["name"] in m.get("workloads", ())}
    assert listed == {m["name"] for m in shared} | {*sparse_own, *NEW_METRICS}
    # One four-chip cell among seven: the quota of a quarter, rounded down.
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) <= max(
        1, len(MANIFEST["workloads"]) // 4)


PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1, "head_dim": 64,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 7168, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
    "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 256,
    "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 8, "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 32000000,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid", "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 129280,
}


def test_the_file_holds_the_published_keys_and_the_cut_is_in_four_counts():
    """Every key of the source's config stands under its own name with its
    own value but the four counts cut (layers, experts held, heads and with
    them KV heads, vocabulary rows), never a width; and the reference works
    the parameters and the state out of the file to the byte."""
    from perf.reference import joyai

    held = _perf_json("configs", f"{CONFIG}.json")
    differ = {k for k, v in PUBLISHED.items() if held.get(k, "absent") != v}
    cut = {"num_hidden_layers": (40, 5), "n_routed_experts": (256, 8),
           "num_attention_heads": (32, 4), "num_key_value_heads": (32, 4),
           "vocab_size": (129280, 16160)}
    assert differ == set(cut) == set(held["reduced"]) == set(held["published"])
    for key, (was, now) in cut.items():
        assert held["published"][key] == was == PUBLISHED[key] and held[key] == now
        assert held["reduced"][key].startswith(f"{was:,} -> {now:,}"), key
    assert not [k for k in held["reduced"] if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"]
    # The router keeps its published width; the share is the first eight.
    assert held["moe_router_outputs"] == 256 and held["moe_first_expert"] == 0
    assert (held["program"], held["reference"], held["rehearsal_config"]) == (
        "joyai_donated", "joyai", PRESET)
    assert held["mesh"] == [1, 1, 1]
    assert held["assumed"]["batch"] == 1 and held["assumed"]["seq_len"] in (4096, 8192)
    assert held["assumed"]["mtp_lambda"] == 0.3
    # What the source's config does not settle: each a line.
    for key in ("mla", "rope", "router", "router_bias", "shared_expert", "dense_layer", "experts",
                "norms", "mtp", "loss", "biases", "precision", "recompute", "optimizer", "init",
                "leaf_names", "seq_len_why", "batch_why"):
        assert isinstance(held["assumed"][key], str) and held["assumed"][key], key
    assert "AFTER its final norm" in held["assumed"]["mtp"] and "FIRST" in held["assumed"]["mtp"]
    assert "32 TPU v5e chips" in held["deployment"] and "nothing stands in" in held["deployment"]
    attention = 2048 * 1536 + 1536 + 1536 * 768 + 2048 * 576 + 512 + 512 * 1024 + 512 * 2048
    expert_layer = 2048 * 256 + 256 + 3 * 2048 * 768 + 3 * 8 * 2048 * 768
    module = 2 * 2048 + 4096 * 2048 + 2048
    assert (attention, expert_layer, module) == (7_079_936, 42_991_872, 8_394_752)
    total = (6 * attention + 3 * 2048 * 7168 + 5 * expert_layer + module + 13 * 2048
             + 2 * 16160 * 2048)
    assert joyai.n_params(held) == total == 376_091_904 == held["parameters"]
    assert joyai.state_bytes(held) == 12 * total + 4 == held["state_bytes"] == 4_513_102_852
    assert all(isinstance(held["limits"][k], float) for k in (
        "loss_gap", "grad_norm_gap", "delta_norm_gap", "grad_diff"))
    assert set(held["limits_why"]) >= set(held["limits"])
    # Routed work only: a token meets 8 x 8 / 256 of a routed expert a layer,
    # and the shared expert whole; the module's block counts at S - 1 positions.
    dense = dict(held, num_experts_per_tok=256)
    per_expert = 6 * 3 * 2048 * 768
    assert joyai.train_flops_per_token(dense, 8192) - joyai.train_flops_per_token(
        held, 8192) == pytest.approx((8 - 0.25) * per_expert * (4 + 8191 / 8192))
    no_module = dict(held, num_nextn_predict_layers=0)
    assert joyai.n_params(held) - joyai.n_params(no_module) == attention + 4096 + (
        expert_layer + module)
    assert joyai.train_flops_per_token(no_module, 8192) < 0.8 * joyai.train_flops_per_token(
        held, 8192)


def test_the_mix_asks_no_more_of_the_storage_than_it_is_known_to_drain():
    """PERF.md 7: a one-chip save cell keeps ``(saves + 1) * state / 53 s``
    (the warm-up take of set-up counts, some 8 s before the window) at or
    under the 0.28 GB/s that the two donated cells have shown steady, and a
    save under 4.9 GB, with two saves a window at the least."""
    from perf.reference import joyai

    mix = _perf_json("traffic", f"{CELL['traffic']}.json")
    held = _perf_json("configs", f"{CONFIG}.json")
    seconds = MANIFEST["run_seconds"]
    saves = sum(1 for k in range(100)
                if float(mix["first_save_s"]) + k * float(mix["save_every_s"]) < seconds)
    assert mix["kind"] == "save_loop" and saves == 2
    state_bytes = joyai.state_bytes(held)
    assert (saves + 1) * state_bytes / (seconds + 8.0) <= 0.28e9 and state_bytes <= 4.9e9
    # The heads whole, or a fifth expert layer, would not.
    for wider in (dict(held, num_attention_heads=32, num_key_value_heads=32),
                  dict(held, num_hidden_layers=6)):
        assert joyai.state_bytes(wider) > 4.9e9


def test_the_whole_cell_rehearses_correct(cache_dir):
    result, stdout = _cell(cache_dir, trace="1")
    # Two saves fall in the window on an idle machine, one on a busy one.
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] >= 1
    checks = _checks(stdout)
    for name in ("restored_bits_differ", "verify_unclean", "resumed_loss_gap", "state_bytes_off",
                 "compile_events_in_window", "tpusnap_warnings"):
        assert checks[name]["value"] == 0, name
    limits = _perf_json("configs", f"{PRESET}.json")["limits"]
    assert {k: checks[k]["limit"] for k in limits} == limits
    # The program donates: every take was waited for until staged (a time: null off the chip).
    assert result["metrics"]["staged_wait_ms"] == {"value": None, "unit": "ms"}
    listed = {m["name"] for m in MANIFEST["per_layer"] if CELL["name"] in m["workloads"]}
    assert set(result["metrics"]) <= listed
    assert {"blocked_ms", "save_durable_s.one_chip", "blobs_per_save",
            "slab_bytes_per_state_byte"} <= set(result["metrics"])
    # No device plane on the CPU: the scopes' shares have nothing to read.
    assert not {"attn_share_of_step", "moe_share_of_step", *NEW_METRICS} & set(result["metrics"])


LEFT_OUT = """
import dataclasses, sys
sys.path.insert(0, {root!r})
from tpusnap.models import joyai
{patch}
sys.argv = ["perf/run.py"] + sys.argv[1:]
from perf import run
sys.exit(run.main())
"""
REPLACED = """
def __init__(self, config):
    self.config = dataclasses.replace(config, {change})
joyai.JoyAI.__init__ = __init__
"""
NO_SHARED_EXPERT = """
sound = joyai._swiglu
joyai._swiglu = lambda u, lp, gate, *rest: (
    0.0 * sound(u, lp, gate, *rest) if gate == "shared_gate" else sound(u, lp, gate, *rest))
"""


@pytest.mark.parametrize("patch", [
    NO_SHARED_EXPERT, REPLACED.format(change="mtp_weight=0.0"),
    REPLACED.format(change="routed_scale=1.0")],
    ids=["the_shared_expert", "the_mtp_term", "the_routed_scale"])
def test_a_term_left_out_of_the_step_is_not_correct(cache_dir, patch):
    """The program drops the shared expert, the module's cross-entropy or the
    2.5 on the routed weights: the state's bytes are the same, the saves are
    sound, and the first steps' gradients are not the reference's."""
    result, stdout = _cell(cache_dir, code=LEFT_OUT.format(root=ROOT, patch=patch))
    assert result["correct"] is False
    failed = [name for name, row in _checks(stdout).items() if not row["ok"]]
    assert "grad_diff" in failed and not {"restored_bits_differ", "state_bytes_off"} & set(failed)


def test_storing_the_state_in_bf16_is_not_correct(cache_dir):
    result, stdout = _cell(cache_dir, "--control", "store_bf16")
    assert result["correct"] is False
    assert '"name": "restored_bits_differ", "ok": false' in stdout


def test_fp8_arithmetic_reads_over_the_limit_of_grad_diff(cache_dir):
    """The reference with its linear layers and expert products rounded to
    fp8 reads over the preset's limit on every seed; the bf16 program reads
    under it."""
    limit = _perf_json("configs", f"{PRESET}.json")["limits"]["grad_diff"]
    proc = _run(cache_dir, "--config", CONFIG, "--seeds", "3", "--first-seed", SEED,
                "--controls", "fp8", "--rehearsal", script="readings.py")
    assert proc.returncode == 0, proc.stderr[-3000:]
    rows = [json.loads(ln.split(": ", 1)[1]) for ln in proc.stdout.splitlines()
            if ln.startswith("perf reading:")]
    assert len(rows) == 3
    for row in rows:
        assert row["sound"]["grad_diff"] <= limit < row["control_fp8"]["grad_diff"], row


# ---- the three readings of the step's scopes


def test_the_three_scopes_share_of_busy_time_on_a_hand_made_line():
    """Twenty busy seconds of one step: a low-rank projection (2 s) and the
    keys' and values' rebuild inside a recomputed query block (3 s); that
    block's scores (4 s); the shared expert's SwiGLU (2 s); a grouped
    product, named by the compiler and by no scope (1 s); the module's
    ``W_eh`` (1 s), its block's rebuild (2 s) and shared expert (1 s), its
    head (2 s); the optimizer's update under no scope (2 s). The module's
    block counts under ``mtp.`` and under its layer's own scopes."""
    from perf.reducers import trace_scope_share as tss

    step = "jit(train_step)/jit(train_step)/"
    events = [_event(f"%fusion.{i} = f32[8] fusion(...)", start, end) for i, (start, end) in
              enumerate([(0, 2), (2, 5), (5, 9), (9, 11), (12, 13), (13, 15), (15, 16), (16, 18),
                         (18, 20)])]
    events.insert(4, _event("%ragged-dot-none.7 = bf16[8,8] custom-call(...)", 11, 12))
    names = dict(zip((e.name for e in events), [
        step + "jvp(checkpoint)/latent.proj/bsd,dr->bsr/dot_general:",
        step + "transpose(jvp(checkpoint))/checkpoint/latent.proj/bsr,rz->bsz/dot_general:",
        step + "transpose(jvp(checkpoint))/checkpoint/attn.global/bqhd,bshd->bhqs/dot_general:",
        step + "jvp(checkpoint)/shared.expert/bsd,df->bsf/dot_general:",
        "ragged-dot-none:",
        step + "jvp()/mtp.combine/bsd,dz->bsz/dot_general:",
        step + "transpose(jvp(checkpoint))/mtp.block/checkpoint/latent.proj/dot_general:",
        step + "jvp(checkpoint)/mtp.block/shared.expert/bsd,df->bsf/dot_general:",
        step + "jvp()/mtp.head/while/body/closed_call/bsd,dv->bsv/dot_general:",
        # The update: under no scope.
    ]))

    def seconds(metric):
        spec = _perf_json("layer_metrics", f"{metric}.json")
        args = spec["args"]
        return tss.scope_seconds(
            events, tss.matcher(args["scope"], args.get("kernels", ()), names))

    inside, busy, matched = seconds("latent_share_of_step")
    assert busy == pytest.approx(20.0) and inside == pytest.approx(7.0)
    assert matched == {"scope": 3}
    inside, _, matched = seconds("shared_expert_share_of_step")
    assert inside == pytest.approx(3.0) and matched == {"scope": 2}
    inside, _, matched = seconds("mtp_share_of_step")
    assert inside == pytest.approx(6.0) and matched == {"scope": 4}
    inside, _, matched = seconds("attn_share_of_step")
    assert inside == pytest.approx(4.0) and matched == {"scope": 1}
    inside, _, matched = seconds("moe_share_of_step")
    assert inside == pytest.approx(1.0) and matched == {"kernel": 1}
    # No trace (an untraced or a CPU run), or a program without the scopes
    # (this change's parent under another cell): nothing to read.
    for metric in NEW_METRICS:
        spec = _perf_json("layer_metrics", f"{metric}.json")
        assert tss.reduce({"trace": None}, **spec["args"]) is None
    bare = {name: "jit(train_step)/jvp()/while/body/dot_general:" for name in names}
    for scope in NEW_METRICS.values():
        assert tss.scope_seconds(events, tss.matcher(scope, (), bare))[0] == 0.0


def test_the_compiled_step_names_its_operations_by_the_scopes_the_readings_look_for():
    """The tiny preset's step, lowered and compiled for this backend: the
    low-rank products carry ``latent.``, forward, recompute and backward
    alike, and the rebuild of keys and values carries it inside a query
    block's recompute; the scores carry ``attn.`` and never ``latent.``; the
    shared expert's products carry ``shared.``; the module's operations
    carry ``mtp.``, its block's among them beside their own scopes, and no
    operation of the main stack does."""
    import re

    import jax

    from perf import harness

    config = _perf_json("configs", f"{PRESET}.json")
    ctx = harness.build_program(config, jax.devices()[:1], int(SEED))
    hlo = ctx.train_step.lower(
        ctx.state, ctx.put_tokens(ctx.next_tokens())).compile().as_text()
    rows = re.findall(r"= \S+ (\w+)\(.*?op_name=\"([^\"]+)\"", hlo)
    has = lambda scope, name: bool(  # noqa: E731
        re.search(r"(?<![A-Za-z0-9_.])" + re.escape(scope), name))
    dots = [name for op, name in rows if op == "dot"]
    assert len(dots) >= 40
    for scope in ("latent.", "attn.", "shared.", "mtp."):
        named = [n for n in dots if has(scope, n)]
        assert [n for n in named if "transpose(" in n] and [
            n for n in named if "transpose(" not in n], scope
    assert not [n for n in dots if has("latent.", n) and has("attn.", n)]
    assert not [n for n in dots if has("shared.", n) and (has("latent.", n) or has("attn.", n))]
    # The rebuild of keys and values (c W_kvb) is inside a query block's recompute.
    assert [n for n in dots if has("latent.", n) and "bsr,rz->bsz" in n and "checkpoint" in n
            and "transpose(" in n]
    # The module: its three scopes, and its block's layer scopes inside ``mtp.block``.
    for scope in ("mtp.combine", "mtp.block", "mtp.head"):
        assert [n for n in dots if has(scope, n)], scope
    inside = [n for n in dots if has("mtp.block", n)]
    assert {s for s in ("latent.", "attn.", "shared.") if [n for n in inside if has(s, n)]} == {
        "latent.", "attn.", "shared."}
    # The main stack's six-of-every-seven operations carry no ``mtp.``.
    main = [n for n in dots if not has("mtp.", n)]
    assert len(main) > len(dots) / 2 and [n for n in main if has("latent.", n)]
