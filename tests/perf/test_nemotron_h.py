"""The hybrid state-space / sparse-expert configuration's cell off the chip:
the manifest's entries and the configuration file (the published keys, the
seven counts cut, what of the published model is left out, the state's bytes
against what the mix asks of the storage); ``perf/run.py --rehearsal`` end to
end at its tiny preset (``EM*M``: an expert layer of 16 routed top 4 with 4
held, two Mamba-2 mixers of 2 heads in 1 group, an attention layer): sound;
with the shared expert, the mixer's skip term or the routed scale left out of
the program's step; with the program's bf16 store switched on; the reference
rounded to fp8 against the limit of ``grad_diff``; and the two readings of
the mixer's scopes, on a hand-made line and in the compiled step's own
names. CPU only; nothing here describes a TPU topology.

No test here carries the marker ``manifest_shape``, though the first three
read the manifest alone: ``tests/perf/test_manifest_grows.py`` lists the
files whose marked tests it runs over a grown copy and holds the set of
them to that list, and a ``model_config`` PR edits no file under
``tests/perf/`` that exists (PERF.md 7, "Open after PR 40" (1): the same as
``test_ouro.py`` and ``test_joyai.py``)."""

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
CONFIG = "nemotron-labs-twotower-30b-a3b"
PRESET = "tiny-nemotron-h"
CELL = next(w for w in MANIFEST["workloads"] if w["config"] == CONFIG)
TWIN = "pythia-410m-24l.save-loop-donated"  # the same mix under another donating step
SPARSE = "smallthinker-21b-a3b.save-loop"  # the other expert layer, the other odd minor dimension
LATENT = "joyai-llm-flash.save-loop-donated"  # the other shared expert, the other sigmoid router
NEW_METRICS = {"ssm_share_of_step": "ssm.", "ssm_scan_share_of_step": "ssm.scan"}
# The shared expert's share is the latent cell's reading (its file, its scope
# ``shared.``), listed for this cell under a name of its own: the latent
# cell's test holds that entry's list to its one cell, and no file under
# ``tests/perf/`` that exists is edited.
SHARED = "shared_expert_share_of_step.relu2"
OTHERS_OWN = {"loop_share_of_step", "exit_share_of_step", "latent_share_of_step",
              "mtp_share_of_step", "shared_expert_share_of_step"}
SEED = "4900000013"


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
        "num_hidden_layers", "n_routed_experts", "mamba_num_heads", "n_groups",
        "num_attention_heads", "num_key_value_heads", "vocab_size"]
    assert (CELL["name"], CELL["traffic"], CELL["chips"]) == (
        f"{CONFIG}.save-loop-donated", "save_loop_20s", 1)
    assert [w["name"] for w in MANIFEST["workloads"] if w["config"] == CONFIG] == [CELL["name"]]
    # Who sees more than its share of the step.
    assert "384 tokens" in CELL["why"] and "attention" in CELL["why"] and "head" in CELL["why"]
    # Wherever the 24-layer donated cell of the same mix is listed, end to end
    # and per layer (``train_tokens_per_s`` and ``staged_wait_ms`` among
    # them), this cell is listed after it.
    shared = [m for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]
              if TWIN in m.get("workloads", ())]
    assert {"train_tokens_per_s", "save_stall_ms", "staged_wait_ms", "hbm_peak_share",
            "slab_bytes_per_state_byte", "slab_pack_ms", "blobs_per_save",
            "save_durable_s.one_chip"} <= {m["name"] for m in shared}
    for metric in shared:
        cells = metric["workloads"]
        assert CELL["name"] in cells and cells.index(CELL["name"]) > cells.index(TWIN), metric
    per_layer = {m["name"]: m for m in MANIFEST["per_layer"]}
    # The expert layer's share and the attention layer's, and the turn on the
    # host of a leaf whose minor dimension is no multiple of 128 (the three
    # ``w_up`` banks' 1856), as the sparse and the latent cell have them.
    others = ("moe_share_of_step", "attn_share_of_step", "relayout_ms",
              "relayout_bytes_per_state_byte")
    for name in others:
        cells = per_layer[name]["workloads"]
        assert cells.index(CELL["name"]) > cells.index(LATENT) > cells.index(SPARSE), name
    for absent in OTHERS_OWN:
        assert CELL["name"] not in per_layer[absent]["workloads"], absent
    # Its own readings: data for the reducer that reads a named scope's share.
    names = [m["name"] for m in MANIFEST["per_layer"]]
    for name, scope in {**NEW_METRICS, SHARED: "shared."}.items():
        assert per_layer[name] == {
            "name": name, "unit": "%", "better": "lower", "source": "device_trace",
            "layer": "train step", "moves": "train_tokens_per_s", "workloads": [CELL["name"]]}
        assert names.index(name) > names.index("scratch_wait_ms")  # appended
        from perf import harness

        spec = harness.layer_metric_spec(name)
        assert spec["reducer"] == "trace_scope_share" and spec["args"] == {"scope": scope}
        assert not spec.get("count")  # a share of device time: never printed from the CPU
    assert not os.path.exists(os.path.join(PERF, "layer_metrics", f"{SHARED}.json"))  # one file a reading
    # Listed wherever this cell is listed: nothing else of the manifest names it.
    listed = {m["name"] for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]
              if CELL["name"] in m.get("workloads", ())}
    assert listed == {m["name"] for m in shared} | {*others, *NEW_METRICS, SHARED}
    # The quota of a quarter, rounded down: one four-chip cell among eight.
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) <= max(
        1, len(MANIFEST["workloads"]) // 4)


PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2, "head_dim": 128,
    "hidden_size": 2688,
    "hybrid_override_pattern": "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 1856, "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
    "mamba_hidden_act": "silu", "mamba_num_heads": 64, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "mlp_bias": False, "mlp_hidden_act": "relu2",
    "model_type": "nemotron_h", "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1, "n_groups": 8,
    "n_routed_experts": 128, "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1, "partial_rotary_factor": 1,
    "rescale_prenorm_residual": True, "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None, "ssm_state_size": 128,
    "tie_word_embeddings": False, "time_step_floor": 0.0001, "time_step_limit": [0, None],
    "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1, "use_bias": False,
    "use_conv_bias": True, "use_mamba_kernels": True, "vocab_size": 131072,
}


def test_the_file_holds_the_published_keys_and_the_cut_is_in_seven_counts():
    """Every key of the source's config stands under its own name with its
    own value but the seven counts cut (layers, and with them the pattern;
    experts held; Mamba-2 heads and their groups; query heads and with them
    KV heads; vocabulary rows), never a width; the file says what of the
    published model is left out; and the reference works the parameters
    and the state out of the file to the byte."""
    from perf.reference import nemotron_h

    held = _perf_json("configs", f"{CONFIG}.json")
    differ = {k for k, v in PUBLISHED.items() if held.get(k, "absent") != v}
    cut = {"num_hidden_layers": (52, 7), "n_routed_experts": (128, 8),
           "mamba_num_heads": (64, 8), "n_groups": (8, 1), "num_attention_heads": (32, 4),
           "num_key_value_heads": (2, 1), "vocab_size": (131072, 16384)}
    # The pattern is the depth's: a string a letter a layer, cut with it.
    assert differ == set(cut) | {"hybrid_override_pattern"}
    assert set(cut) == set(held["reduced"])
    assert set(held["published"]) == set(cut) | {"hybrid_override_pattern"}
    for key, (was, now) in cut.items():
        assert held["published"][key] == was == PUBLISHED[key] and held[key] == now
        assert held["reduced"][key].startswith(f"{was:,} -> {now:,}"), key
    pattern = PUBLISHED["hybrid_override_pattern"]
    assert held["published"]["hybrid_override_pattern"] == pattern and len(pattern) == 52
    assert held["hybrid_override_pattern"] == "EMEMEM*" == pattern[6:13]  # layers 6-12
    assert pattern[6:34] == "EMEMEM*" * 4  # the unit stands four times in a row
    assert (pattern.count("M"), pattern.count("E"), pattern.count("*")) == (23, 23, 6)
    assert "EMEMEM*" in held["reduced"]["num_hidden_layers"]
    # No width: not a hidden, intermediate, state or head size, nor the experts a token meets.
    assert not [k for k in held["reduced"] if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"]
    for width, value in (("hidden_size", 2688), ("mamba_head_dim", 64), ("ssm_state_size", 128),
                         ("conv_kernel", 4), ("chunk_size", 128), ("moe_intermediate_size", 1856),
                         ("moe_shared_expert_intermediate_size", 3712), ("head_dim", 128),
                         ("num_experts_per_tok", 6), ("routed_scaling_factor", 2.5)):
        assert held[width] == value, width
    # The router keeps its published width; the share is the first eight.
    assert held["moe_router_outputs"] == 128 and held["moe_first_expert"] == 0
    assert (held["program"], held["reference"], held["rehearsal_config"]) == (
        "nemotron_h_donated", "nemotron_h", PRESET)
    assert held["mesh"] == [1, 1, 1]
    assert held["assumed"]["batch"] == 1 and held["assumed"]["seq_len"] in (4096, 8192)
    # What the source's config does not settle: each a line.
    for key in ("mamba", "scan", "conv", "dt_and_decays", "gated_norm", "router", "router_bias",
                "shared_expert", "experts", "attention", "norms", "loss", "biases", "unused_keys",
                "precision", "recompute", "optimizer", "init", "leaf_names", "seq_len_why",
                "batch_why", "left_out"):
        assert isinstance(held["assumed"][key], str) and held["assumed"][key], key
    # What is left out of the published model, with the reason, and nothing
    # in the tree that stands in for it.
    left_out = held["assumed"]["left_out"]
    for said in ("second tower", "conditioning", "diffusion", "not_given", "no key"):
        assert said in left_out, said
    assert "No rotary" in held["assumed"]["attention"]
    assert "16 TPU v5e chips" in held["deployment"] and "nothing stands in" in held["deployment"]
    mixer = 2688 + 2688 * 1288 + 768 * 4 + 768 + 3 * 8 + 512 + 512 * 2688
    expert_layer = 2688 + 2688 * 128 + 128 + 2 * 8 * 2688 * 1856 + 2 * 2688 * 3712
    attention = 2688 + 2 * 2688 * 512 + 2 * 2688 * 128
    assert (mixer, expert_layer, attention) == (4_845_464, 100_125_440, 3_443_328)
    total = 3 * mixer + 3 * expert_layer + attention + 2688 + 2 * 16384 * 2688
    assert nemotron_h.n_params(held) == total == 406_439_112 == held["parameters"]
    assert nemotron_h.state_bytes(held) == 12 * total + 4 == held["state_bytes"] == 4_877_269_348
    assert all(isinstance(held["limits"][k], float) for k in (
        "loss_gap", "grad_norm_gap", "delta_norm_gap", "grad_diff"))
    assert set(held["limits_why"]) >= set(held["limits"])
    # Routed work only: a token meets 6 x 8 / 128 of a routed expert a layer,
    # and the shared expert whole; the recurrence counts as it is stated.
    dense = dict(held, num_experts_per_tok=128)
    per_expert = 6 * 2 * 2688 * 1856
    assert nemotron_h.train_flops_per_token(dense, 8192) - nemotron_h.train_flops_per_token(
        held, 8192) == pytest.approx((8 - 0.375) * per_expert * 3)
    no_mixers = dict(held, num_hidden_layers=4, hybrid_override_pattern="EEE*")
    per_mixer = 6 * (2688 * 1288 + 512 * 2688) + 12 * 8 * 64 * 128 + 6 * 4 * 768
    assert nemotron_h.train_flops_per_token(held, 8192) - nemotron_h.train_flops_per_token(
        no_mixers, 8192) == pytest.approx(3 * per_mixer)


def test_the_mix_asks_no_more_of_the_storage_than_it_is_known_to_drain():
    """PERF.md 7: a one-chip save cell keeps ``(saves + 1) * state / 53 s``
    (the warm-up take of set-up counts, some 8 s before the window) at or
    under the 0.28 GB/s that the donated cells have shown steady, and a
    save under 4.9 GB, with two saves a window at the least."""
    from perf.reference import nemotron_h

    mix = _perf_json("traffic", f"{CELL['traffic']}.json")
    held = _perf_json("configs", f"{CONFIG}.json")
    seconds = MANIFEST["run_seconds"]
    saves = sum(1 for k in range(100)
                if float(mix["first_save_s"]) + k * float(mix["save_every_s"]) < seconds)
    assert mix["kind"] == "save_loop" and saves == 2
    state_bytes = nemotron_h.state_bytes(held)
    assert (saves + 1) * state_bytes / (seconds + 8.0) <= 0.28e9 and state_bytes <= 4.9e9
    # The stretched unit of nine layers, the mixer's heads whole, sixteen
    # experts held or sixteen query heads would not.
    for wider in (dict(held, num_hidden_layers=9, hybrid_override_pattern="EMEMEMEM*"),
                  dict(held, mamba_num_heads=64, n_groups=8),
                  dict(held, n_routed_experts=16), dict(held, num_attention_heads=16)):
        assert nemotron_h.state_bytes(wider) > 4.9e9


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
    # No device plane on the CPU: the scopes' shares have nothing to read, and
    # the line leaves them out.
    assert not {"attn_share_of_step", "moe_share_of_step", SHARED, *NEW_METRICS} & set(
        result["metrics"])


LEFT_OUT = """
import dataclasses, sys
sys.path.insert(0, {root!r})
from tpusnap.models import nemotron_h
from tpusnap.models.nemotron_h import NemotronH
{patch}
sys.argv = ["perf/run.py"] + sys.argv[1:]
from perf import run
sys.exit(run.main())
"""
NO_SHARED_EXPERT = """
sound = NemotronH.shared
NemotronH.shared = lambda self, lp, u: 0.0 * sound(self, lp, u)
"""
NO_SKIP_TERM = """
sound = NemotronH.mamba
NemotronH.mamba = lambda self, lp, u: sound(self, {**lp, "D": 0.0 * lp["D"]}, u)
"""
NO_ROUTED_SCALE = """
def __init__(self, config):
    self.config = dataclasses.replace(config, routed_scale=1.0)
NemotronH.__init__ = __init__
"""


@pytest.mark.parametrize("patch", [NO_SHARED_EXPERT, NO_SKIP_TERM, NO_ROUTED_SCALE],
                         ids=["the_shared_expert", "the_skip_term", "the_routed_scale"])
def test_a_term_left_out_of_the_step_is_not_correct(cache_dir, patch):
    """The program drops the shared expert, the mixer's ``D x`` or the 2.5 on
    the routed weights: the state's bytes are the same, the saves are sound,
    and the first steps' gradients are not the reference's."""
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


# ---- the readings of the step's scopes


def test_the_mixers_share_of_busy_time_on_a_hand_made_line():
    """Twenty busy seconds of one step: a mixer's ``W_in`` (2 s); its
    convolution (1 s); a product inside a chunk (3 s) and the chain over the
    chunks, a ``while`` of 2 s with an update of 1 s in its body, in the
    backward; the gated norm (1 s); attention's scores (3 s); the shared
    expert (3 s); the held experts' product over every token (2 s); the
    optimizer's update under no scope (3 s). ``ssm.scan`` is a part of
    ``ssm.``; a loop's body counts under its own operation."""
    from perf.reducers import trace_scope_share as tss

    step = "jit(train_step)/jit(train_step)/"
    spans = [(0, 2), (2, 3), (3, 6), (6, 8), (6.5, 7.5), (8, 9), (9, 12), (12, 15), (15, 17),
             (17, 20)]
    events = [_event(f"%fusion.{i} = f32[8] fusion(...)", start, end)
              for i, (start, end) in enumerate(spans)]
    names = dict(zip((e.name for e in events), [
        step + "jvp(checkpoint)/ssm.proj/bsd,dz->bsz/dot_general:",
        step + "transpose(jvp(checkpoint))/checkpoint/ssm.conv/mul:",
        step + "transpose(jvp(checkpoint))/checkpoint/ssm.scan/bnghts,bnsghp->bntghp/dot_general:",
        step + "transpose(jvp(checkpoint))/ssm.scan/while:",
        step + "transpose(jvp(checkpoint))/ssm.scan/while/body/mul:",
        step + "jvp(checkpoint)/ssm.proj/rsqrt:",
        step + "transpose(jvp(checkpoint))/checkpoint/attn.global/bqkgd,bskd->bkgqs/dot_general:",
        step + "jvp(checkpoint)/shared.expert/bsd,df->bsf/dot_general:",
        step + "jvp(checkpoint)/moe.experts/td,edf->tef/dot_general:",
        # The update: under no scope.
    ]))

    def seconds(metric):
        from perf import harness

        args = harness.layer_metric_spec(metric)["args"]
        return tss.scope_seconds(
            events, tss.matcher(args["scope"], args.get("kernels", ()), names))

    inside, busy, matched = seconds("ssm_share_of_step")
    assert busy == pytest.approx(20.0) and inside == pytest.approx(9.0)
    assert matched == {"scope": 6}
    inside, _, matched = seconds("ssm_scan_share_of_step")
    assert inside == pytest.approx(5.0) and matched == {"scope": 3}
    inside, _, matched = seconds(SHARED)
    assert inside == pytest.approx(3.0) and matched == {"scope": 1}
    inside, _, matched = seconds("attn_share_of_step")
    assert inside == pytest.approx(3.0) and matched == {"scope": 1}
    inside, _, matched = seconds("moe_share_of_step")
    assert inside == pytest.approx(2.0) and matched == {"scope": 1}
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
    mixer's projections carry ``ssm.proj``, the products of a chunk and the
    chain over chunks ``ssm.scan``, forward (or recompute) and backward alike;
    attention's scores carry ``attn.`` and the shared expert's products
    ``shared.``, and neither carries ``ssm.``; no operation carries two of
    the mixer's three scopes."""
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
    assert len(dots) >= 20
    for scope in ("ssm.proj", "shared."):
        named = [n for n in dots if has(scope, n)]
        assert [n for n in named if "transpose(" in n] and [
            n for n in named if "transpose(" not in n], scope
    # The products of a chunk, the one with the entering state and
    # attention's scores (at the preset's sizes this backend turns them into
    # fused multiply-and-sums: the framework's name stays), and the chain
    # between chunks, a loop.
    scan = [name for _, name in rows if has("ssm.scan", name) or has("attn.global", name)]
    for product in ("bntgz,bnsgz->bngts", "bnghts,bnsghp->bntghp", "bnsghp,bnsgz->bnghpz",
                    "bnghpz,bntgz->bntghp", "bqkgd,bskd->bkgqs"):
        assert [n for n in scan if product in n], product
    assert [n for n in scan if "transpose(" in n] and [n for n in scan if "transpose(" not in n]
    loops = re.findall(r" while\(.*?op_name=\"([^\"]+)\"", hlo)
    assert [n for n in loops if has("ssm.scan", n) and "transpose(" in n] and [
        n for n in loops if has("ssm.scan", n) and "transpose(" not in n]
    # The convolution is elementwise: no product, but operations of its own.
    assert [name for _, name in rows if has("ssm.conv", name)]
    mixer = ("ssm.proj", "ssm.conv", "ssm.scan")
    assert not [n for _, n in rows if sum(has(s, n) for s in mixer) > 1]
    assert not [n for _, n in rows if has("ssm.", n) and (
        has("attn.", n) or has("shared.", n) or has("moe.", n))]
    outside = [n for n in dots if not has("ssm.", n)]
    assert len(outside) > len(dots) / 3 and [n for n in outside if has("shared.", n)]
