"""The looped configuration's cell off the chip: the manifest's entries
and the configuration file (the published keys, the cut in depth alone,
the state's bytes against what the mix asks of the storage); ``perf/run.py
--rehearsal`` end to end at its tiny preset (2 layers run 4 times): sound;
with a pass or the entropy term left out of the program's step; with the
program's bf16 store switched on; the reference rounded to fp8 against the
limit of ``grad_diff``; and the two readings of the step's scopes, on a
hand-made line and in the traced rehearsal's result. CPU only; nothing
here describes a TPU topology."""

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
CONFIG = "ouro-2.6b"
CELL = next(w for w in MANIFEST["workloads"] if w["config"] == CONFIG)
TWIN = "pythia-410m-24l.save-loop-donated"  # the same mix, the same bytes, another step
NEW_METRICS = {"loop_share_of_step": "loop.", "exit_share_of_step": "exit."}
SEED = "4000000013"


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
    proc = _run(cache_dir, "--workload", CELL["name"], "--seed", SEED, "--seconds", "1.7",
                "--trace", trace, "--rehearsal", *extra, code=code)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout


def _checks(stdout):
    rows = [json.loads(ln.split(": ", 1)[1]) for ln in stdout.splitlines()
            if ln.startswith("perf check: ")]
    return {r["name"]: r for r in rows}


def test_the_manifest_has_the_configuration_and_its_one_cell():
    entry = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    held = _perf_json("configs", f"{CONFIG}.json")
    assert entry["source"] == held["source"] and entry["file"] == f"perf/configs/{CONFIG}.json"
    assert entry["reduced"] == list(held["reduced"]) == ["num_hidden_layers"]
    assert (CELL["name"], CELL["traffic"], CELL["chips"]) == (
        f"{CONFIG}.save-loop-donated", "save_loop_20s", 1)
    assert [w["name"] for w in MANIFEST["workloads"] if w["config"] == CONFIG] == [CELL["name"]]
    # Wherever the 24-layer cell of the same mix is listed, end to end and
    # per layer (``staged_wait_ms`` among them), this cell is listed after it.
    shared = [m for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]
              if TWIN in m.get("workloads", ())]
    assert {"train_tokens_per_s", "save_stall_ms", "staged_wait_ms", "hbm_peak_share",
            "save_durable_s.one_chip"} <= {m["name"] for m in shared}
    for metric in shared:
        cells = metric["workloads"]
        assert CELL["name"] in cells and cells.index(CELL["name"]) > cells.index(TWIN), metric
    per_layer = {m["name"]: m for m in MANIFEST["per_layer"]}
    assert CELL["name"] in per_layer["attn_share_of_step"]["workloads"]
    for absent in ("moe_share_of_step", "relayout_ms", "relayout_bytes_per_state_byte"):
        assert CELL["name"] not in per_layer[absent]["workloads"], absent
    # Its own two readings: data for the reducer that reads a named scope's share.
    names = [m["name"] for m in MANIFEST["per_layer"]]
    for name, scope in NEW_METRICS.items():
        assert per_layer[name] == {
            "name": name, "unit": "%", "better": "lower", "source": "device_trace",
            "layer": "train step", "moves": "train_tokens_per_s", "workloads": [CELL["name"]]}
        assert names.index(name) > names.index("staged_wait_ms")  # appended
        spec = _perf_json("layer_metrics", f"{name}.json")
        assert spec["reducer"] == "trace_scope_share" and spec["args"] == {"scope": scope}
        assert not spec.get("count")  # a share of device time: never printed from the CPU
    # Listed wherever this cell is listed: nothing else of the manifest names it.
    listed = {m["name"] for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]
              if CELL["name"] in m.get("workloads", ())}
    assert listed == {m["name"] for m in shared} | {"attn_share_of_step", *NEW_METRICS}


def test_the_file_holds_the_published_keys_and_the_cut_is_in_depth_alone():
    """Every key of the source's config stands under its own name with its
    own value but the depth; the reference works the parameters and the
    state out of the file to the byte."""
    from perf.reference import ouro

    held = _perf_json("configs", f"{CONFIG}.json")
    published = {
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5632,
        "layer_types": ["full_attention"] * 48, "max_position_embeddings": 65536,
        "max_window_layers": 48, "model_type": "ouro", "num_attention_heads": 16,
        "num_hidden_layers": 48, "num_key_value_heads": 16, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
        "tie_word_embeddings": False, "total_ut_steps": 4, "early_exit_threshold": 1,
        "use_sliding_window": False, "vocab_size": 49152,
    }
    differ = {k for k, v in published.items() if held.get(k, "absent") != v}
    assert differ == {"num_hidden_layers"} == set(held["reduced"])
    assert held["num_hidden_layers"] == 4 and held["published"] == {"num_hidden_layers": 48}
    assert (held["program"], held["reference"], held["rehearsal_config"]) == (
        "ouro_donated", "ouro", "tiny-ouro")
    assert held["mesh"] == [1, 1, 1]
    assert held["assumed"]["batch"] == 1 and held["assumed"]["seq_len"] in (2048, 4096)
    assert held["assumed"]["beta"] == 0.1
    # What the source's config does not hold and its modeling file does: each a line.
    for key in ("norms", "final_norm", "gate", "biases", "loss", "rope", "precision",
                "recompute", "optimizer", "init", "batch_why"):
        assert isinstance(held["assumed"][key], str) and held["assumed"][key], key
    assert "pipeline" in held["deployment"] and "48 -> 4" in held["reduced"]["num_hidden_layers"]
    per_layer = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
    assert per_layer == 51_388_416
    assert ouro.n_params(held) == 4 * per_layer + 2 * 49152 * 2048 + 2048 + 2049 == 406_884_353
    assert held["parameters"] == 406_884_353
    assert ouro.state_bytes(held) == 12 * 406_884_353 + 4 == held["state_bytes"] == 4_882_612_240
    assert all(isinstance(held["limits"][k], float) for k in (
        "loss_gap", "grad_norm_gap", "delta_norm_gap", "grad_diff"))
    assert set(held["limits_why"]) >= set(held["limits"])
    # Four passes of the layers held and four heads; the recompute not counted.
    matrix = 4 * (4 * (per_layer - 4 * 2048) + 49152 * 2048 + 2048)
    assert ouro.train_flops_per_token(held, 4096) == pytest.approx(
        6.0 * matrix + 16 * 12.0 * 2048 * 4097 / 2)
    one_pass = dict(held, total_ut_steps=1)
    assert ouro.n_params(one_pass) == ouro.n_params(held)  # a pass more holds nothing more
    assert ouro.train_flops_per_token(held, 4096) == pytest.approx(
        4 * ouro.train_flops_per_token(one_pass, 4096))


def test_the_mix_asks_no_more_of_the_storage_than_it_is_known_to_drain():
    """PERF.md 7: a one-chip save cell keeps ``(saves + 1) * state / 53 s``
    (the warm-up take of set-up counts, some 8 s before the window) under
    what the work directory's mount drains, with two saves a window at the
    least; this state is within half a per cent of the 24-layer cell's."""
    from perf.reference import decoder, ouro

    mix = _perf_json("traffic", f"{CELL['traffic']}.json")
    held = _perf_json("configs", f"{CONFIG}.json")
    seconds = MANIFEST["run_seconds"]
    saves = sum(1 for k in range(100)
                if float(mix["first_save_s"]) + k * float(mix["save_every_s"]) < seconds)
    assert mix["kind"] == "save_loop" and saves == 2
    state_bytes = ouro.state_bytes(held)
    assert (saves + 1) * state_bytes / (seconds + 8.0) <= 0.33e9
    twin = _perf_json("configs", "pythia-410m-24l.json")
    assert abs(state_bytes / decoder.state_bytes(twin) - 1) < 0.005
    # Two layers more would not: 6.12 GB a save.
    six_layers = ouro.state_bytes(dict(held, num_hidden_layers=6))
    assert (saves + 1) * six_layers / (seconds + 8.0) > 0.33e9


def test_the_whole_cell_rehearses_correct(cache_dir):
    result, stdout = _cell(cache_dir, trace="1")
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] == 2
    checks = _checks(stdout)
    for name in ("restored_bits_differ", "verify_unclean", "resumed_loss_gap", "state_bytes_off",
                 "compile_events_in_window", "tpusnap_warnings"):
        assert checks[name]["value"] == 0, name
    limits = _perf_json("configs", "tiny-ouro.json")["limits"]
    assert {k: checks[k]["limit"] for k in limits} == limits
    # The program donates: every take was waited for until staged (a time: null off the chip).
    assert result["metrics"]["staged_wait_ms"] == {"value": None, "unit": "ms"}
    listed = {m["name"] for m in MANIFEST["per_layer"] if CELL["name"] in m["workloads"]}
    assert set(result["metrics"]) <= listed
    assert {"blocked_ms", "save_durable_s.one_chip", "blobs_per_save"} <= set(result["metrics"])
    # No device plane on the CPU: the scopes' shares have nothing to read.
    assert not {"attn_share_of_step", *NEW_METRICS} & set(result["metrics"])


LEFT_OUT = """
import dataclasses, sys
sys.path.insert(0, {root!r})
from tpusnap.models import ouro
def __init__(self, config):
    self.config = dataclasses.replace(config, {change})
ouro.Ouro.__init__ = __init__
sys.argv = ["perf/run.py"] + sys.argv[1:]
from perf import run
sys.exit(run.main())
"""


@pytest.mark.parametrize("change", ["n_passes=config.n_passes - 1", "entropy_weight=0.0"],
                         ids=["a_pass", "the_entropy_term"])
def test_a_term_of_the_loss_left_out_of_the_step_is_not_correct(cache_dir, change):
    """The program runs one pass fewer than the configuration says, or drops
    ``beta * H(p)``: the state's bytes are the same, the saves are sound,
    and the first steps' gradients are not the reference's."""
    result, stdout = _cell(cache_dir, code=LEFT_OUT.format(root=ROOT, change=change))
    assert result["correct"] is False
    failed = [name for name, row in _checks(stdout).items() if not row["ok"]]
    assert "grad_diff" in failed and not {"restored_bits_differ", "state_bytes_off"} & set(failed)


def test_storing_the_state_in_bf16_is_not_correct(cache_dir):
    result, stdout = _cell(cache_dir, "--control", "store_bf16")
    assert result["correct"] is False
    assert '"name": "restored_bits_differ", "ok": false' in stdout


def test_fp8_arithmetic_reads_over_the_limit_of_grad_diff(cache_dir):
    """The reference with its linear layers rounded to fp8 reads over the
    preset's limit on every seed; the bf16 program reads under it."""
    limit = _perf_json("configs", "tiny-ouro.json")["limits"]["grad_diff"]
    proc = _run(cache_dir, "--config", CONFIG, "--seeds", "3", "--first-seed", SEED,
                "--controls", "fp8", "--rehearsal", script="readings.py")
    assert proc.returncode == 0, proc.stderr[-3000:]
    rows = [json.loads(ln.split(": ", 1)[1]) for ln in proc.stdout.splitlines()
            if ln.startswith("perf reading:")]
    assert len(rows) == 3
    for row in rows:
        assert row["sound"]["grad_diff"] <= limit < row["control_fp8"]["grad_diff"] / 2, row


# ---- the two readings of the step's scopes


def _event(name, start, end):
    return types.SimpleNamespace(name=name, start_ns=int(start * 1e9),
                                 duration_ns=int((end - start) * 1e9))


def test_the_loops_and_the_exits_share_of_busy_time_on_a_hand_made_line():
    """Twenty busy seconds of one step: the pass loop's ``while`` (13 s) with
    a projection (4 s), an attention fusion of a recomputed block (3 s) and
    the backward of the SwiGLU (4 s) in its body; a head's product (3 s) and
    the gate's entropy term (1 s); the optimizer's update under no scope
    (3 s). Each metric's scope is the one its file gives."""
    from perf.reducers import trace_scope_share as tss

    step = "jit(train_step)/jit(train_step)/"
    events = [
        _event("%while.7 = (f32[8]) while(...)", 0.0, 13.0),
        _event("%fusion.1 = bf16[8] fusion(...)", 0.5, 4.5),
        _event("%fusion.2 = f32[8] fusion(...)", 4.5, 7.5),
        _event("%fusion.3 = bf16[8] fusion(...)", 8.0, 12.0),
        _event("%fusion.4 = f32[8] fusion(...)", 13.0, 16.0),
        _event("%fusion.5 = f32[8] fusion(...)", 16.0, 17.0),
        _event("%loop_fusion.6 = f32[8] fusion(...)", 17.0, 20.0),
    ]
    names = {
        events[0].name: step + "jvp()/while/body/closed_call/loop.pass/while:",
        events[1].name: step + "jvp()/while/body/closed_call/loop.pass/while/body/closed_call/"
                               "checkpoint/loop.pass/bsd,dz->bsz/dot_general:",
        events[2].name: "loop.pass/attn.global/checkpoint/reduce_max:",
        events[3].name: step + "transpose(jvp())/while/body/closed_call/loop.pass/while/body/"
                               "closed_call/checkpoint/loop.pass/bsd,df->bsf/dot_general:",
        events[4].name: step + "jvp()/while/body/closed_call/exit.head/while/body/closed_call/"
                               "bsd,dv->bsv/dot_general:",
        events[5].name: step + "transpose(jvp())/while/body/closed_call/exit.gate/mul:",
        # The update: under no scope, and its own name (a loop fusion) is no scope either.
    }

    def seconds(metric):
        spec = _perf_json("layer_metrics", f"{metric}.json")
        return tss.scope_seconds(events, tss.matcher(spec["args"]["scope"], (), names))

    inside, busy, matched = seconds("loop_share_of_step")
    # The loop's own 2 s (13 s less its body's 11 s) count under the scope too.
    assert busy == pytest.approx(20.0) and inside == pytest.approx(13.0)
    assert matched == {"scope": 4}
    inside, _, matched = seconds("exit_share_of_step")
    assert inside == pytest.approx(4.0) and matched == {"scope": 2}
    inside, _, matched = seconds("attn_share_of_step")
    assert inside == pytest.approx(3.0) and matched == {"scope": 1}
    # No trace (an untraced or a CPU run), or a program without the scopes
    # (this change's parent under another cell): nothing to read.
    for metric in NEW_METRICS:
        spec = _perf_json("layer_metrics", f"{metric}.json")
        assert tss.reduce({"trace": None}, **spec["args"]) is None
    bare = {name: "jit(train_step)/jvp()/while/body/dot_general:" for name in names}
    assert tss.scope_seconds(events, tss.matcher("loop.", (), bare))[0] == 0.0


def test_the_compiled_step_names_its_operations_by_the_scopes_the_readings_look_for():
    """The tiny preset's step, lowered and compiled for this backend: every
    matrix product of the step carries ``loop.`` or ``exit.`` in its
    framework name, forward, recompute and backward alike; the attention
    blocks' carry ``attn.`` inside ``loop.``; nothing carries both ``loop.``
    and ``exit.``."""
    import re

    import jax

    from perf import harness

    config = _perf_json("configs", "tiny-ouro.json")
    ctx = harness.build_program(config, jax.devices()[:1], int(SEED))
    hlo = ctx.train_step.lower(
        ctx.state, ctx.put_tokens(ctx.next_tokens())).compile().as_text()
    rows = re.findall(r"= \S+ (\w+)\(.*?op_name=\"([^\"]+)\"", hlo)
    has = lambda scope, name: bool(  # noqa: E731
        re.search(r"(?<![A-Za-z0-9_.])" + re.escape(scope), name))
    dots = [name for op, name in rows if op == "dot"]
    assert len(dots) >= 20
    assert all(has("loop.", n) != has("exit.", n) for n in dots), [
        n for n in dots if has("loop.", n) == has("exit.", n)][:3]
    assert any(has("loop.", n) and "transpose(" in n for n in dots)
    assert any(has("exit.", n) and "transpose(" in n for n in dots)
    named = [name for _, name in rows]
    assert [n for n in named if has("attn.", n)] and all(
        has("loop.", n) for n in named if has("attn.", n))
    assert not [n for n in named if has("loop.", n) and has("exit.", n)]
