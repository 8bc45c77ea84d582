"""The benchmark's harness off the chip: the manifest and the files it
names, the reducers on hand-made observations, and ``perf/run.py
--rehearsal`` end to end at the tiny configuration: sound, with the
program's bf16 store switched on (the control), and with the train step
broken underneath; and the seam by which a configuration's file names its
program and its plain reference, driven through a pair that is not the
flagship's. CPU only; nothing here describes a TPU topology."""

import copy
import json
import math
import os
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PERF = os.path.join(ROOT, "perf")
sys.path.insert(0, ROOT)

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
CELLS = {w["name"]: w for w in MANIFEST["workloads"]}
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _perf_json(*parts):
    with open(os.path.join(PERF, *parts)) as f:
        return json.load(f)


def _one_cell_per_traffic_kind():
    seen = {}
    for cell in MANIFEST["workloads"]:
        kind = _perf_json("traffic", f"{cell['traffic']}.json")["kind"]
        seen.setdefault(kind, cell["name"])
    return sorted(seen.items())


SAVE_LOOP_CELL = next(c["name"] for c in MANIFEST["workloads"] if c["traffic"] == "save_loop")


def one_chip_save_loop_cells(manifest=MANIFEST):
    """The one-chip cells whose mix is of kind ``save_loop``, in the
    manifest's order: the cells that share the write path's readings."""
    return [w["name"] for w in manifest["workloads"] if w["chips"] == 1
            and _perf_json("traffic", f"{w['traffic']}.json")["kind"] == "save_loop"]


def within_the_four_chip_quota(manifest):
    """The contract's rule, for any number of cells: of a benchmark's cells
    a quarter, rounded down, may ask for four chips, and one always may."""
    four_chip = [w for w in manifest["workloads"] if w["chips"] == 4]
    return len(four_chip) <= max(1, len(manifest["workloads"]) // 4)


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    return str(tmp_path_factory.mktemp("perf_jax_cache"))


def _run(cache_dir, *args, code=None):
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=1",
        JAX_COMPILATION_CACHE_DIR=cache_dir,
    )
    cmd = [sys.executable, "-c", code] if code else [sys.executable, os.path.join(PERF, "run.py")]
    return subprocess.run(
        [*cmd, *args], capture_output=True, text=True, timeout=300, cwd=ROOT, env=env
    )


def _result(proc):
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.manifest_shape
def test_manifest_names_files_that_exist():
    for config in MANIFEST["configs"]:
        path = os.path.join(ROOT, config["file"])
        assert os.path.isfile(path), path
        held = _perf_json("configs", f"{config['name']}.json")
        assert sorted(held["reduced"]) == sorted(config["reduced"])
        assert os.path.isfile(os.path.join(PERF, "configs", f"{held['rehearsal_config']}.json"))
    for cell in MANIFEST["workloads"]:
        assert cell["config"] in {c["name"] for c in MANIFEST["configs"]}
        traffic = _perf_json("traffic", f"{cell['traffic']}.json")
        assert os.path.isfile(os.path.join(PERF, "traffic", f"{traffic['kind']}.py"))
    from perf import harness

    for metric in MANIFEST["per_layer"]:
        spec = harness.layer_metric_spec(metric["name"])
        assert os.path.isfile(os.path.join(PERF, "reducers", f"{spec['reducer']}.py"))
    # One file a reading: a quantity split by what it moves shares its file.
    held = {f[:-5] for f in os.listdir(os.path.join(PERF, "layer_metrics"))}
    named = {m["name"] for m in MANIFEST["per_layer"]}
    assert held <= named | {n.rsplit(".", 1)[0] for n in named}
    assert _perf_json("peaks.json")["devices"]


@pytest.mark.manifest_shape
def test_every_moves_is_an_end_to_end_metric_its_cells_report():
    reported = {
        m["name"]: set(m.get("workloads", CELLS)) for m in MANIFEST["end_to_end"]
    }
    for metric in MANIFEST["per_layer"]:
        assert metric["moves"] in reported, metric
        for cell in metric.get("workloads", CELLS):
            assert cell in CELLS
            assert cell in reported[metric["moves"]], (metric["name"], cell)
    for cell in CELLS:  # setup_s, one more end-to-end metric, one per-layer metric
        assert cell in reported["setup_s"]
        assert any(cell in cells for name, cells in reported.items() if name != "setup_s")
        assert any(cell in m.get("workloads", CELLS) for m in MANIFEST["per_layer"])


@pytest.mark.manifest_shape
def test_names_and_units_hold_only_the_allowed_characters():
    metrics = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(set(names)) == len(names)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for cell in MANIFEST["workloads"]:
        assert all(NAME.match(cell[k]) for k in ("name", "config", "traffic")), cell
        assert len(cell["why"]) <= 200 and cell["chips"] in (1, 4)
    for config in MANIFEST["configs"]:
        assert all(NAME.match(k) for k in [config["name"], *config["reduced"]])


@pytest.mark.manifest_shape
@pytest.mark.parametrize("cell", [c for c in one_chip_save_loop_cells() if c != SAVE_LOOP_CELL])
def test_a_one_chip_save_loop_cell_is_listed_wherever_the_first_one_is(cell):
    """One write path, one set of readings: a later one-chip cell of a
    ``save_loop`` mix drops none of the metrics that the first reports."""
    for metric in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        listed = metric.get("workloads", CELLS)
        if SAVE_LOOP_CELL in listed:
            assert cell in listed, metric["name"]


@pytest.mark.manifest_shape
def test_the_four_chip_cells_keep_to_their_quota():
    assert within_the_four_chip_quota(MANIFEST)


SHARED_REFERENCE = "first_steps"  # what every architecture's reference shares
PROGRAM_FUNCTIONS = ("build",)
REFERENCE_FUNCTIONS = ("sizes", "n_params", "state_bytes", "train_flops_per_token",
                       "init_params", "loss_fn")


def _modules(folder):
    return {f[:-3] for f in os.listdir(os.path.join(PERF, folder)) if f.endswith(".py")}


@pytest.mark.manifest_shape
def test_no_cell_or_configuration_is_named_in_code():
    names = set(CELLS) | {c["name"] for c in MANIFEST["configs"]}
    for folder, _, files in os.walk(PERF):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(folder, name)) as f:
                    text = f.read()
                assert not [n for n in names if n in text], os.path.join(folder, name)
    # What every cell runs through names no program and no architecture
    # either, and imports no model: both are found by the configuration's keys.
    named = (_modules("programs") | _modules("reference")) - {SHARED_REFERENCE}
    assert {"transformer", "decoder"} <= named
    for name in ("harness.py", "run.py", "readings.py"):
        with open(os.path.join(PERF, name)) as f:
            text = f.read()
        assert not [n for n in sorted(named) if n in text], name
        assert "tpusnap.models" not in text, name


def test_reducers_on_hand_made_observations():
    from perf.reducers import (
        bytes_per_state_byte, counter_per_op, memory_share, percentile, span_per_op,
    )

    span = lambda name, start, end, nbytes=0: {  # noqa: E731
        "name": name, "start": start, "end": end, "bytes": nbytes}
    obs = {
        "ops": [{"t_call": 0.0, "t_done": 10.0}, {"t_call": 20.0, "t_done": 30.0}],
        "spans": [
            span("dtoh", 1.0, 2.0, 600), span("dtoh", 1.5, 3.0, 400),  # two threads
            span("dtoh", 21.0, 24.0, 1000), span("storage_write", 2.0, 2.5),
            span("dtoh", 12.0, 13.0, 9999),  # between operations: nobody's
        ],
        "counters": [
            {"name": "dtoh.enqueued_bytes", "t": 0.5, "delta": 1000},
            {"name": "dtoh.enqueued_bytes", "t": 20.5, "delta": 1000},
        ],
        "series": {"step_ms": list(range(1, 201))},
        "state_bytes": 1000,
        "memory": [{"peak_bytes_in_use": 60, "bytes_limit": 100},
                   {"peak_bytes_in_use": 80, "bytes_limit": 100}],
    }
    assert span_per_op.per_op(obs, {"dtoh"}) == [2.5, 3.0]
    assert span_per_op.reduce(obs, ["dtoh"]) == pytest.approx(2750.0)  # ms, busy not wall
    assert span_per_op.reduce(obs, ["commit_prep"]) is None  # nothing to read
    assert counter_per_op.reduce(obs, ["dtoh.enqueued_bytes"]) == 1000
    assert bytes_per_state_byte.reduce(
        obs, counters=["dtoh.enqueued_bytes"], spans=["dtoh"]) == pytest.approx(2.0)
    assert percentile.reduce(obs, "step_ms", 99) == 198
    assert percentile.reduce(obs, "step_ms", 50) == 100
    assert percentile.reduce(obs, "no_such_series", 50) is None
    assert memory_share.reduce(obs) == pytest.approx(80.0)
    assert memory_share.reduce({"memory": None}) is None


def test_trace_reduction_on_synthetic_intervals():
    from perf.reducers import _trace, trace_idle_share

    busy = _trace.merge([(0.0, 1.0), (0.5, 2.0), (5.0, 6.0), (9.0, 9.5)])
    assert busy == [[0.0, 2.0], [5.0, 6.0], [9.0, 9.5]]
    spans = [{"name": "async_blocked", "start": 2.0, "end": 4.5},
             {"name": "dtoh", "start": 4.0, "end": 5.0}]
    gaps = _trace.label_gaps(busy, (0.0, 10.0), spans)
    assert gaps == [["host-other", 3.5], ["async_blocked", 3.0]]  # longest label first
    events = [(0, 10, "while"), (1, 3, "fusion.1"), (3, 6, "fusion.2"), (12, 13, "fusion.1")]
    assert _trace.self_seconds(events) == {"fusion.1": 3.0, "fusion.2": 3.0, "while": 5.0}
    assert _trace.short_name("%fusion.7 = f32[8]{0} fusion(...)") == "fusion.7"
    obs = {"trace": {"busy_s": 3.5, "window_s": 10.0}}
    assert trace_idle_share.reduce(obs) == pytest.approx(65.0)
    assert trace_idle_share.reduce({"trace": None}) is None


def test_without_a_tpu_nothing_is_printed(cache_dir):
    cell = next(iter(CELLS))
    proc = _run(cache_dir, "--workload", cell, "--seed", "1", "--seconds", "1", "--trace", "0")
    assert proc.returncode not in (0, None)
    assert not [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    assert "needs" in proc.stderr


@pytest.mark.parametrize("kind,cell", _one_cell_per_traffic_kind())
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_runs_each_traffic_kind_end_to_end(cache_dir, kind, cell, trace):
    from perf import harness

    proc = _run(cache_dir, "--workload", cell, "--seed", "2147483653", "--seconds", "1",
                "--trace", str(trace), "--rehearsal")
    result = _result(proc)
    assert set(result) - {"breakdown", "checks"} == RESULT_KEYS
    # Each number compared stands beside its limit under the line's last key
    # and on the last lines of standard error.
    assert list(result)[-1] == "checks" and result["checks"]["failed"] == {"value": 0, "limit": 0}
    compared = [ln for ln in proc.stderr.splitlines() if ln.startswith("perf compared: ")]
    assert [ln.split()[2] for ln in compared] == list(result["checks"])
    assert proc.stderr.rstrip().splitlines()[-1] == compared[-1]
    assert all(row["value"] <= row["limit"] for row in result["checks"].values())
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] >= 1
    assert result["device"]["platform"] == "cpu"
    wanted = MANIFEST["per_layer"] if trace else MANIFEST["end_to_end"]
    units = {m["name"]: m["unit"] for m in wanted if cell in m.get("workloads", CELLS)}
    assert result["metrics"] and set(result["metrics"]) <= set(units)
    for name, metric in result["metrics"].items():
        assert metric["unit"] == units[name]
        # A time from the CPU is never printed under a metric's name; a
        # count (bytes per byte, fallbacks) may be.
        counted = harness.layer_metric_spec(name).get("count") if trace else False
        assert metric["value"] is None or counted, (name, metric)
    assert "busy_s" not in result["device"]
    assert '"compile_events_in_window", "ok": true' in proc.stdout


@pytest.mark.parametrize("kind,cell", _one_cell_per_traffic_kind())
def test_storing_the_state_in_bf16_is_not_correct(cache_dir, kind, cell):
    """The control: the program's own lower-precision store switched on."""
    proc = _run(cache_dir, "--workload", cell, "--seed", "7", "--seconds", "1",
                "--trace", "0", "--rehearsal", "--control", "store_bf16")
    assert _result(proc)["correct"] is False
    assert '"name": "restored_bits_differ", "ok": false' in proc.stdout


def test_eight_bit_arithmetic_fails_the_first_order_number(cache_dir):
    """The control of the train step's check: the reference with its linear
    layers rounded to fp8, the step below the configuration's bf16, reads
    over the limit of ``grad_diff`` on every seed, and the bf16 program
    under it."""
    config = _perf_json("configs", f"{MANIFEST['configs'][0]['name']}.json")
    limit = _perf_json("configs", f"{config['rehearsal_config']}.json")["limits"]["grad_diff"]
    proc = subprocess.run(
        [sys.executable, os.path.join(PERF, "readings.py"), "--config",
         MANIFEST["configs"][0]["name"], "--seeds", "3", "--controls", "fp8", "--rehearsal"],
        capture_output=True, text=True, timeout=300, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=cache_dir),
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    rows = [json.loads(ln.split(": ", 1)[1]) for ln in proc.stdout.splitlines()
            if ln.startswith("perf reading:")]
    assert len(rows) == 3
    for row in rows:
        assert row["sound"]["grad_diff"] <= limit < row["control_fp8"]["grad_diff"] / 2, row


BROKEN_STEP = """
import sys
sys.path.insert(0, {root!r})
import tpusnap.models as models
sound = models.make_train_step
def broken(model, mesh, *a, **k):
    step = sound(model, mesh, *a, **k)
    return lambda state, tokens: (state, step(state, tokens)[1])  # the state never moves
models.make_train_step = broken
sys.argv = ["perf/run.py"] + sys.argv[1:]
from perf import run
sys.exit(run.main())
"""


def test_a_step_that_returns_its_state_unchanged_is_not_correct(cache_dir):
    """The rest of a run, chip look-up skipped, with the timed path broken."""
    cell = _one_cell_per_traffic_kind()[0][1]
    proc = _run(cache_dir, "--workload", cell, "--seed", "11", "--seconds", "1",
                "--trace", "0", "--rehearsal", code=BROKEN_STEP.format(root=ROOT))
    assert _result(proc)["correct"] is False
    assert '"name": "delta_norm_gap", "ok": false' in proc.stdout


# ---- the seam: a configuration names its program and its plain reference

STUB_REFERENCE = '''
"""A token model of two matrices with a residual: none of the decoder's keys."""
import jax
import jax.numpy as jnp


def sizes(config):
    return {"vocab": int(config["vocab_size"]), "width": int(config["two_matrix_width"])}


def n_params(config):
    c = sizes(config)
    return 2 * c["vocab"] * c["width"]


def state_bytes(config):
    return 12 * n_params(config) + 8  # two counters, not one


def train_flops_per_token(config, seq_len):
    c = sizes(config)
    return 6.0 * c["vocab"] * c["width"]


def init_params(key, c):
    table, readout = jax.random.split(key)
    return {
        "readout": jax.random.normal(readout, (c["width"], c["vocab"])) * c["width"] ** -0.5,
        "table": jax.random.normal(table, (c["vocab"], c["width"])) * c["width"] ** -0.5,
    }


def loss_fn(params, tokens, c, quant=None):
    x = params["table"][tokens]
    logits = jnp.matmul(x + jnp.tanh(x), params["readout"], precision="highest")
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    return -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1).mean()
'''

# The program makes its own weights and takes its own step; {hidden} is the
# hidden layer, with or without the residual term.
STUB_PROGRAM = '''
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def build(config, devices, key):
    vocab, width = int(config["vocab_size"]), int(config["two_matrix_width"])
    mesh = Mesh(np.asarray(devices), ("all",))
    everywhere = NamedSharding(mesh, P())

    def init(key):
        table, readout = jax.random.split(key)
        params = {{
            "readout": jax.random.normal(readout, (width, vocab)) * width ** -0.5,
            "table": jax.random.normal(table, (vocab, width)) * width ** -0.5,
        }}
        zeros = lambda: jax.tree.map(jnp.zeros_like, params)
        return {{"params": params, "seen": jnp.zeros((), jnp.int32),
                "opt": {{"mu": zeros(), "nu": zeros(), "count": jnp.zeros((), jnp.int32)}}}}

    shardings = jax.tree.map(lambda _: everywhere, jax.eval_shape(init, key))

    def loss_fn(params, tokens):
        x = params["table"][tokens]
        logits = jnp.matmul({hidden}, params["readout"], precision="highest")
        logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
        return -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1).mean()

    def train_step(state, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(state["params"], tokens)
        count = state["opt"]["count"] + 1
        mu = jax.tree.map(lambda m, g: 0.9 * m + 0.1 * g, state["opt"]["mu"], grads)
        nu = jax.tree.map(lambda n, g: 0.999 * n + 0.001 * g * g, state["opt"]["nu"], grads)
        unbias = lambda beta: 1.0 - beta ** count.astype(jnp.float32)
        params = jax.tree.map(
            lambda p, m, n: p - 1e-3 * (m / unbias(0.9)) / (jnp.sqrt(n / unbias(0.999)) + 1e-8),
            state["params"], mu, nu)
        return {{"params": params, "seen": state["seen"] + tokens.size,
                "opt": {{"mu": mu, "nu": nu, "count": count}}}}, loss

    return {{
        "mesh": mesh,
        "state": jax.jit(init, out_shardings=shardings)(key),
        "train_step": jax.jit(train_step, out_shardings=(shardings, everywhere)),
        "state_shardings": shardings,
        "token_sharding": everywhere,
    }}
'''

STUB_CONFIG = {
    "source": "none: a test's stand-in for a configuration of another architecture",
    "program": "two_matrix", "reference": "two_matrix",
    "vocab_size": 96, "two_matrix_width": 24, "assumed": {"batch": 2, "seq_len": 16},
    "limits": {"loss_gap": 1e-4, "grad_norm_gap": 1e-3, "delta_norm_gap": 1e-3,
               "grad_diff": 1e-3},
}
STUB_CONFIGS = {  # name -> what its file changes of STUB_CONFIG
    "two-matrix": {},
    "two-matrix-no-residual": {"program": "two_matrix_no_residual"},
    "names-no-program": {"program": None},
    "names-no-reference": {"reference": None},
    "names-a-program-that-is-not-there": {"program": "three_matrix"},
    "names-a-reference-that-is-not-there": {"reference": "three_matrix"},
}


@pytest.fixture(scope="module")
def checkout_with_another_architecture(tmp_path_factory):
    """A copy of the benchmark to which a ``model_config`` PR's files are
    added, and in which no file that was there is edited: configurations,
    one program (and its broken twin), one reference, and their entries in
    a manifest that keeps every metric."""
    root = tmp_path_factory.mktemp("checkout")
    perf = root / "perf"
    shutil.copytree(PERF, perf, ignore=shutil.ignore_patterns("__pycache__"))
    (perf / "reference" / "two_matrix.py").write_text(STUB_REFERENCE)
    (perf / "programs" / "two_matrix.py").write_text(
        STUB_PROGRAM.format(hidden="x + jnp.tanh(x)"))
    (perf / "programs" / "two_matrix_no_residual.py").write_text(
        STUB_PROGRAM.format(hidden="jnp.tanh(x)"))
    manifest = copy.deepcopy(MANIFEST)
    manifest["configs"], manifest["workloads"] = [], []
    for name, changes in STUB_CONFIGS.items():
        held = {k: v for k, v in {**STUB_CONFIG, **changes}.items() if v is not None}
        (perf / "configs" / f"{name}.json").write_text(
            json.dumps({**held, "rehearsal_config": name}))
        manifest["configs"].append(
            {"name": name, "source": held["source"], "file": f"perf/configs/{name}.json",
             "reduced": [], "why": "a stand-in"})
        manifest["workloads"].append(
            {"name": f"{name}.save-loop", "config": name, "traffic": "save_loop",
             "chips": 1, "why": "a stand-in"})
    cells = [w["name"] for w in manifest["workloads"]]
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if "workloads" in metric:
            metric["workloads"] = cells if SAVE_LOOP_CELL in metric["workloads"] else []
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return str(root)


def _run_in(checkout, cache_dir, config, trace=0):
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=1",
        JAX_COMPILATION_CACHE_DIR=cache_dir,
        PYTHONPATH=ROOT,  # the library under test; ``perf`` is the checkout's own
    )
    return subprocess.run(
        [sys.executable, os.path.join(checkout, "perf", "run.py"), "--workload",
         f"{config}.save-loop", "--seed", "2147483659", "--seconds", "1", "--trace",
         str(trace), "--rehearsal"],
        capture_output=True, text=True, timeout=300, cwd=checkout, env=env,
    )


@pytest.mark.parametrize("config,sound", [("two-matrix", True), ("two-matrix-no-residual", False)])
def test_a_configuration_of_another_architecture_is_files_only(
        checkout_with_another_architecture, cache_dir, config, sound):
    """A whole rehearsal through a program and a reference that share none
    of the flagship's code or keys; with one term left out of the program's
    step it is not correct."""
    proc = _run_in(checkout_with_another_architecture, cache_dir, config, trace=int(sound))
    result = _result(proc)
    assert result["correct"] is sound and result["failed"] == 0 and result["attempted"] >= 1
    assert '"name": "state_bytes_off", "ok": true' in proc.stdout
    assert ('"name": "grad_diff", "ok": true' in proc.stdout) is sound
    if sound:  # the same per-layer names as the flagship's cell under this mix
        assert set(result["metrics"]) <= {
            m["name"] for m in MANIFEST["per_layer"] if SAVE_LOOP_CELL in m["workloads"]}
        assert result["metrics"]["stored_bytes_per_state_byte"]["value"] == 1.0


@pytest.mark.parametrize("config", [n for n in STUB_CONFIGS if n.startswith("names-")])
def test_a_configuration_that_names_no_program_or_reference_prints_nothing(
        checkout_with_another_architecture, cache_dir, config):
    """No default and no fallback: exit 2, as without the chip."""
    proc = _run_in(checkout_with_another_architecture, cache_dir, config)
    assert proc.returncode == 2, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert not [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    wanted = "program" if "program" in config else "reference"
    assert f"names no {wanted} that exists" in proc.stderr


@pytest.mark.manifest_shape
@pytest.mark.parametrize("name", sorted(os.listdir(os.path.join(PERF, "configs"))))
def test_every_configuration_names_a_program_and_a_reference_that_exist(name):
    from perf import harness

    config = _perf_json("configs", name)
    program, reference = (harness.config_module(config, k) for k in ("program", "reference"))
    assert os.path.isfile(os.path.join(PERF, "programs", f"{config['program']}.py"))
    assert os.path.isfile(os.path.join(PERF, "reference", f"{config['reference']}.py"))
    assert config["reference"] != SHARED_REFERENCE
    assert all(callable(getattr(program, f, None)) for f in PROGRAM_FUNCTIONS)
    assert all(callable(getattr(reference, f, None)) for f in REFERENCE_FUNCTIONS)


@pytest.mark.manifest_shape
@pytest.mark.parametrize("preset", sorted(
    {_perf_json("configs", f"{c['name']}.json")["rehearsal_config"] for c in MANIFEST["configs"]}))
def test_the_program_keeps_its_contract_and_the_references_state_bytes(preset):
    """What ``perf/README.md`` asks of a program, at the rehearsal presets:
    the state is ``{"params", "opt": {"mu", "nu"}, ...}`` with float32
    moments, its parameters' leaf paths and shapes are the reference's, and
    its bytes are what the reference works out from the sizes."""
    import jax
    import jax.numpy as jnp

    from perf import harness

    config = _perf_json("configs", f"{preset}.json")
    ctx = harness.build_program(config, jax.devices()[:math.prod(config["mesh"])], 2147483659)
    reference = harness.config_module(config, "reference")
    assert ctx.state_bytes == reference.state_bytes(config)
    want = jax.eval_shape(lambda k: reference.init_params(k, reference.sizes(config)),
                          harness.seed_key(1))
    shapes = lambda tree: {p: (x.shape, x.dtype) for p, x in zip(  # noqa: E731
        harness._leaf_paths(tree), jax.tree.leaves(tree))}
    assert {dtype for _, dtype in shapes(want).values()} == {jnp.dtype("float32")}
    for tree in (ctx.state["params"], ctx.state["opt"]["mu"], ctx.state["opt"]["nu"]):
        assert shapes(tree) == shapes(want)
    for key in ("mesh", "state", "train_step", "state_shardings", "token_sharding"):
        assert hasattr(ctx, key)
