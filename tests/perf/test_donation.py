"""A program whose step donates its state (``"donates": True``), off the
chip: the harness and the traffic kind ``save_loop`` read no state after
``train_step`` has had it and wait for ``wait_staged()`` before the step
that deletes what a take was handed; a program that donates nothing goes
through the first steps as it always did, to the last bit; a kind that has
not been taught donation refuses such a program; the new cell's rehearsal
and its mix's interval. CPU only; no time read here stands for a device's."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PERF = os.path.join(ROOT, "perf")
sys.path.insert(0, ROOT)

from perf import harness  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
CELL = "pythia-410m-24l.save-loop-donated"
CONFIG = "pythia-410m-24l"
SEED = 3600000007


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    return str(tmp_path_factory.mktemp("perf_jax_cache"))


def _run(cache_dir, *args, code=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1",
               JAX_COMPILATION_CACHE_DIR=cache_dir)
    cmd = [sys.executable, "-c", code] if code else [sys.executable, os.path.join(PERF, "run.py")]
    return subprocess.run([*cmd, *args], capture_output=True, text=True, timeout=400,
                          cwd=ROOT, env=env)


def _result(proc):
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _window(proc):
    return next(json.loads(ln.split(": ", 1)[1]) for ln in proc.stdout.splitlines()
                if ln.startswith("perf window: "))


# ---- (a) a step that deletes what it is handed

# The test double: the flagship's step, which donates nothing, and then
# ``delete()`` on every leaf of the state it was handed. The CPU backend may
# leave ``donate_argnums`` aside; a deleted buffer it cannot: whoever reads
# one raises. ``async_take`` and ``wait_staged`` say when they returned.
DELETING_STEP = """
import sys
sys.path.insert(0, {root!r})
import jax
from perf.programs import transformer, transformer_donated
from tpusnap import snapshot

def say(what):
    print("double: " + what, flush=True)

def build(config, devices, key):
    built = transformer.build(config, devices, key)
    sound = built["train_step"]
    def train_step(state, tokens):
        say("step")
        out = jax.block_until_ready(sound(state, tokens))
        for leaf in jax.tree.leaves(state):
            leaf.delete()
        return out
    return dict(built, train_step=train_step, donates=True)
transformer_donated.build = build

take, staged = snapshot.Snapshot.async_take, snapshot.PendingSnapshot.wait_staged
def async_take(*a, **k):
    pending = take(*a, **k)
    say("take")
    return pending
def wait_staged(self, *a, **k):
    out = staged(self, *a, **k)
    say("staged" if out and self.staged() else "not staged")
    return out
snapshot.Snapshot.async_take = staticmethod(async_take)
snapshot.PendingSnapshot.wait_staged = wait_staged

sys.argv = ["perf/run.py"] + sys.argv[1:]
from perf import run
sys.exit(run.main())
"""


def test_no_state_is_read_after_the_step_has_had_it(cache_dir):
    """A whole rehearsal of ``save_loop`` and the first steps before it over
    a step that deletes its input: it ends ``correct`` only if neither the
    harness nor the kind reads a state it has passed on, and every take, the
    warm-up's included, was staged before the next step began."""
    proc = _run(cache_dir, "--workload", CELL, "--seed", str(SEED), "--seconds", "1.7",
                "--trace", "0", "--rehearsal", code=DELETING_STEP.format(root=ROOT))
    result = _result(proc)
    assert result["correct"] is True and result["failed"] == 0
    assert result["checks"]["restored_bits_differ"] == {"value": 0, "limit": 0}
    events = [ln.split(": ", 1)[1] for ln in proc.stdout.splitlines() if ln.startswith("double: ")]
    takes = [k for k, e in enumerate(events) if e == "take"]
    assert len(takes) == result["attempted"] + 1  # the warm-up's take is the first
    assert events[:3] == ["step"] * 3  # the first steps, before any take
    for k in takes:
        assert events[k + 1] == "staged", events[k:k + 3]
    assert "not staged" not in events
    window = _window(proc)
    assert len(window["staged_wait_ms"]) == result["attempted"]
    assert all(ms >= 0.0 for ms in window["staged_wait_ms"])


def test_the_donating_program_deletes_the_state_it_is_handed():
    """``transformer_donated`` at its rehearsal preset: the step's input is
    gone after the step (this backend honours ``donate_argnums``), the output
    is a whole state, and the harness is told."""
    import jax

    config = harness.read_json("configs", "tiny-1-donated.json")
    ctx = harness.build_program(config, jax.devices()[:1], SEED)
    assert ctx.donates is True
    before = ctx.state
    ctx.state, loss = ctx.train_step(before, ctx.put_tokens(ctx.next_tokens()))
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(before))
    assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(ctx.state))
    assert jax.tree.structure(ctx.state) == jax.tree.structure(ctx.state_shapes)
    assert float(loss) > 0.0
    plain = harness.build_program(harness.read_json("configs", "tiny-1.json"),
                                  jax.devices()[:1], SEED)
    assert plain.donates is False


# ---- (b) a program that donates nothing goes through the first steps as before

# The four numbers of ``tiny-1`` on SEED, read on the tree before
# ``program_first_steps`` was taught donation (PR 36's parent) and on the
# tree after it: the same bits.
PINNED = {
    "loss_gap": "0x1.82542ef8cc7cbp-13",
    "grad_norm_gap": "0x1.c915b0fe7f8eap-11",
    "delta_norm_gap": "0x1.87db828381f9dp-11",
    "grad_diff": "0x1.eb529eb19e987p-7",
}


def _first_steps_as_they_stood(ctx, tokens):
    """``harness.program_first_steps`` as it was until PR 36, line for line:
    references to the starting parameters and to the first step's first
    moment, which a step that donates nothing leaves alive."""
    import jax
    import jax.numpy as jnp

    norms = jax.jit(
        lambda tree: jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(x))), tree)
    )
    delta_norms = jax.jit(
        lambda a, b: jax.tree.map(lambda x, y: jnp.sqrt(jnp.sum(jnp.square(x - y))), a, b)
    )
    params0 = ctx.state["params"]
    paths = harness._leaf_paths(params0)
    losses, grad_norms, first_mu = [], None, None
    for batch in tokens:
        ctx.state, loss = ctx.train_step(ctx.state, ctx.put_tokens(batch))
        losses.append(float(loss))
        if grad_norms is None:
            b1 = harness.first_steps_module().ADAM["b1"]
            first_mu = dict(zip(paths, jax.tree.leaves(ctx.state["opt"]["mu"])))
            got = jax.device_get(norms(ctx.state["opt"]["mu"]))
            grad_norms = {
                p: float(v) / (1.0 - b1) for p, v in zip(paths, jax.tree.leaves(got))
            }
    got = jax.device_get(delta_norms(ctx.state["params"], params0))
    return {
        "losses": losses,
        "grad_norms": grad_norms,
        "delta_norms": {p: float(v) for p, v in zip(paths, jax.tree.leaves(got))},
        "first_mu": first_mu,
    }


@pytest.fixture(scope="module")
def tiny_gaps():
    """The four gaps of ``tiny-1`` by the harness and by the path that stood,
    and of ``tiny-1-donated`` by the harness: one reference for all three."""
    import jax

    config = harness.read_json("configs", "tiny-1.json")
    tokens = harness.first_tokens(config, SEED)
    devices = jax.devices()[:1]
    want = harness.reference_first_steps(config, harness.seed_key(SEED), tokens, devices)
    gaps = {}
    for name, preset, first_steps in (
        ("now", "tiny-1", harness.program_first_steps),
        ("stood", "tiny-1", _first_steps_as_they_stood),
        ("donated", "tiny-1-donated", harness.program_first_steps),
    ):
        ctx = harness.build_program(harness.read_json("configs", f"{preset}.json"), devices, SEED)
        gaps[name] = harness.first_step_gaps(first_steps(ctx, tokens), want)
    return gaps


@pytest.mark.parametrize("number", sorted(PINNED))
def test_the_first_steps_of_a_program_that_donates_nothing_read_as_before(tiny_gaps, number):
    """Equal to the last bit: the edit gave a donating program copies and a
    program that donates nothing the references it always had."""
    assert tiny_gaps["now"][number] == tiny_gaps["stood"][number]
    assert tiny_gaps["now"][number].hex() == PINNED[number]


def test_donation_changes_no_number_of_the_first_steps(tiny_gaps):
    """The same step compiled with ``donate_argnums=0``, read off the
    harness's own copies: the same four numbers."""
    assert tiny_gaps["donated"] == tiny_gaps["now"]


def test_fingerprints_outlive_the_state_and_tell_one_altered_element():
    """What ``save_loop`` keeps of a state that the next step deletes: equal
    for equal bits, from one program a tree shape (the window compiles
    nothing); another for one element altered in its last bit, for two
    elements exchanged, for a leaf of another type, shape or count."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    ctx = harness.build_program(harness.read_json("configs", "tiny-1-donated.json"),
                                jax.devices()[:1], SEED)
    host = jax.tree.map(np.asarray, ctx.state)
    prints = ctx.fingerprints(ctx.state)
    for leaf in jax.tree.leaves(ctx.state):
        leaf.delete()
    put = lambda tree: jax.device_put(tree, ctx.state_shardings)  # noqa: E731
    count = lambda tree: harness.count_fingerprint_mismatches(ctx, prints, tree)  # noqa: E731
    assert count(put(host)) == 0
    assert ctx._fingerprints._cache_size() == 1

    def altered(edit):
        tree = jax.tree.map(np.array, host)
        edit(tree)
        return tree

    def last_bit(tree):
        w = tree["params"]["layers"]["w2"]
        w.view(np.uint32)[-1, -1, -1] ^= 1

    def exchanged(tree):
        w = tree["opt"]["nu"]["embed"]
        w[0, 0], w[3, 5] = 0.25, 0.5
        tree["opt"]["mu"]["embed"][0, 0], tree["opt"]["mu"]["embed"][3, 5] = 0.5, 0.25
        tree["opt"]["nu"]["embed"][...] = tree["opt"]["mu"]["embed"][[*range(4, 256), 0, 1, 2, 3]]

    assert count(put(altered(last_bit))) == 1
    same_sum = altered(exchanged)  # nu/embed: mu/embed's rows in another order, the same plain sum
    got = jax.device_get(ctx.fingerprints(put(same_sum)))
    assert got["opt"]["nu"]["embed"][0] == got["opt"]["mu"]["embed"][0]
    assert got["opt"]["nu"]["embed"][1] != got["opt"]["mu"]["embed"][1]
    bf16 = altered(lambda t: None)
    bf16["params"]["ln_f"] = jnp.asarray(bf16["params"]["ln_f"], jnp.bfloat16)
    assert count({**put(host), "params": {**put(host)["params"], "ln_f": bf16["params"]["ln_f"]}}) == 1
    assert count({"params": put(host)["params"]}) == len(jax.tree.leaves(host))


# ---- (c) a kind that has not been taught donation

UNDER_ANOTHER_KIND = """
import argparse, json, sys, time
sys.path.insert(0, {root!r})
from perf import harness
with open({manifest!r}) as f:
    manifest = json.load(f)
cell = {{"name": "a-donating-program.resume", "config": {config!r}, "traffic": "resume_loop",
        "chips": 1, "why": "a test's"}}
args = argparse.Namespace(workload=cell["name"], seed=7, seconds=1.0, trace=0, rehearsal=True,
                          control=None)
sys.exit(harness.run_cell(manifest, cell, args, time.monotonic()))
"""


def test_a_donating_program_under_resume_loop_prints_nothing(cache_dir):
    """``resume_loop`` steps from one state again and again: it says nothing
    of donation, so the harness ends the run as it does without a chip."""
    code = UNDER_ANOTHER_KIND.format(root=ROOT, config=CONFIG,
                                     manifest=os.path.join(ROOT, "BENCHMARK.json"))
    proc = _run(cache_dir, code=code)
    assert proc.returncode == 2, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert not [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    assert "donates its state" in proc.stderr and "'resume_loop'" in proc.stderr
    assert "No result is printed" in proc.stderr
    from perf.traffic import resume_loop, save_loop

    assert save_loop.SERVES_A_DONATING_STEP is True
    assert not hasattr(resume_loop, "SERVES_A_DONATING_STEP")


# ---- (d) the new cell's rehearsal

@pytest.mark.parametrize("control", [None, "store_bf16"])
def test_the_rehearsal_of_the_donated_cell(cache_dir, control):
    """Two saves, each waited for until staged; ``staged_wait_ms`` among
    the per-layer names (a time: null off the chip) beside every reading the
    16-layer cell carries; with the bf16 store switched on, not correct."""
    extra = ["--control", control] if control else []
    proc = _run(cache_dir, "--workload", CELL, "--seed", "3600000008", "--seconds", "1.7",
                "--trace", "1", "--rehearsal", *extra)
    result = _result(proc)
    window = _window(proc)
    assert result["attempted"] == window["saves_durable"] == 2 and result["failed"] == 0
    assert len(window["staged_wait_ms"]) == 2
    assert result["metrics"]["staged_wait_ms"] == {"value": None, "unit": "ms"}
    if control:
        assert result["correct"] is False
        assert '"name": "restored_bits_differ", "ok": false' in proc.stdout
        return
    assert result["correct"] is True
    wanted = {m["name"] for m in MANIFEST["per_layer"] if CELL in m["workloads"]}
    flagship = {m["name"] for m in MANIFEST["per_layer"]
                if "pythia-410m.save-loop" in m["workloads"]}
    assert wanted == flagship | {"staged_wait_ms"}
    assert set(result["metrics"]) <= wanted
    assert {"blocked_ms", "save_durable_s.one_chip", "blobs_per_save"} <= set(result["metrics"])


def test_a_program_that_donates_nothing_is_not_waited_for(cache_dir):
    """The 16-layer cell's rehearsal: no ``staged_wait_ms`` on its window's
    line and none among its metrics: its loop gets the early hand-back."""
    proc = _run(cache_dir, "--workload", "pythia-410m.save-loop", "--seed", "3600000009",
                "--seconds", "1", "--trace", "1", "--rehearsal")
    result = _result(proc)
    assert result["correct"] is True
    assert "staged_wait_ms" not in _window(proc) and "staged_wait_ms" not in result["metrics"]


# ---- the rest of a run with the timed path broken underneath

# One element of the largest leaf altered on its way into every take: the
# answer is wrong where it is produced, and nothing else is.
ALTERED_SAVE = """
import sys
sys.path.insert(0, {root!r})
import jax
from perf.traffic import save_loop
sound = save_loop._take
def _take(ctx, n):
    kept = ctx.state
    leaves, tree = jax.tree.flatten(kept)
    k = max(range(len(leaves)), key=lambda i: leaves[i].size)
    first = (0,) * leaves[k].ndim
    leaves[k] = leaves[k].at[first].set(leaves[k][first] * 2 + 1)
    ctx.state = jax.tree.unflatten(tree, leaves)
    try:
        return sound(ctx, n)
    finally:
        ctx.state = kept
save_loop._take = _take
sys.argv = ["perf/run.py"] + sys.argv[1:]
from perf import run
sys.exit(run.main())
"""


@pytest.mark.parametrize("cell", [CELL, "pythia-410m.save-loop"])
def test_one_element_altered_on_its_way_into_a_save_is_not_correct(cache_dir, cell):
    """Of some 98,000 elements one: a leaf's fingerprints under a donating
    step, an element's bits where nothing is donated."""
    proc = _run(cache_dir, "--workload", cell, "--seed", "3600000010", "--seconds", "1.7",
                "--trace", "0", "--rehearsal", code=ALTERED_SAVE.format(root=ROOT))
    result = _result(proc)
    assert result["correct"] is False and result["failed"] == 0
    assert result["checks"]["restored_bits_differ"] == {"value": 1, "limit": 0}


def test_a_donating_step_that_returns_its_state_unchanged_is_not_correct(cache_dir):
    from test_harness import BROKEN_STEP

    proc = _run(cache_dir, "--workload", CELL, "--seed", "11", "--seconds", "1.7",
                "--trace", "0", "--rehearsal", code=BROKEN_STEP.format(root=ROOT))
    assert _result(proc)["correct"] is False
    assert '"name": "delta_norm_gap", "ok": false' in proc.stdout


# ---- (e) the mix's interval, (f) the manifest's entries

@pytest.mark.manifest_shape
def test_the_mix_asks_no_more_of_the_storage_than_it_is_known_to_drain():
    """What PERF.md 6 found (PR 34, PR 36): the work directory's mount drains
    0.33 GB/s and not more, and the warm-up take of set-up counts: two saves
    of this configuration's state a window stay under it; three, with the
    warm-up take some 8 s before the window, are 0.37 GB/s and did not drain."""
    mix = harness.read_json("traffic", "save_loop_20s.json")
    config = harness.read_json("configs", f"{CONFIG}.json")
    state_bytes = harness.config_module(config, "reference").state_bytes(config)
    assert state_bytes == 12 * 405_062_656 + 4
    seconds = MANIFEST["run_seconds"]
    saves = sum(1 for k in range(100)
                if float(mix["first_save_s"]) + k * float(mix["save_every_s"]) < seconds)
    assert saves == 2
    warm_up_before_s = 8.0
    assert (saves + 1) * state_bytes / (seconds + warm_up_before_s) <= 0.33e9
    assert (saves + 2) * state_bytes / (seconds + warm_up_before_s) > 0.33e9
    flagship = harness.read_json("traffic", "save_loop.json")
    assert mix["kind"] == flagship["kind"] == "save_loop"
    for key in ("trace_lead_s", "trace_max_s", "env"):
        assert mix[key] == flagship[key], key
    assert (mix["first_save_s"], mix["save_every_s"]) == (8.0, 20.0)
    assert (flagship["first_save_s"], flagship["save_every_s"]) == (2.0, 11.0)


@pytest.mark.manifest_shape
def test_the_manifests_entries_for_the_donated_cell():
    cells = {w["name"]: w for w in MANIFEST["workloads"]}
    configs = {c["name"]: c for c in MANIFEST["configs"]}
    assert (cells[CELL]["config"], cells[CELL]["traffic"], cells[CELL]["chips"]) == (
        CONFIG, "save_loop_20s", 1)
    held = harness.read_json("configs", f"{CONFIG}.json")
    flagship = harness.read_json("configs", "pythia-410m.json")
    assert configs[CONFIG]["reduced"] == [] and held["reduced"] == {}
    assert configs[CONFIG]["source"] == configs["pythia-410m"]["source"] == held["source"]
    assert held["num_hidden_layers"] == 24 and held["program"] == "transformer_donated"
    assert held["rehearsal_config"] == "tiny-1-donated"
    for key in flagship:  # the published widths and the reference: the 16-layer file's
        if key not in ("deployment", "program", "num_hidden_layers", "reduced", "assumed",
                       "rehearsal_config", "limits"):
            assert held[key] == flagship[key], key
    # Two limits are this depth's own, set from its own readings (PERF.md 6, PR 36).
    assert held["limits"] == {**flagship["limits"], "loss_gap": 0.0005, "delta_norm_gap": 0.01}
    for key, value in flagship["assumed"].items():
        if key != "batch":
            assert held["assumed"][key] == value, key
    assert held["assumed"]["batch"] in (1, 2, 3, 4)
    end_to_end = {m["name"]: m for m in MANIFEST["end_to_end"]}
    assert CELL in end_to_end["train_tokens_per_s"]["workloads"]
    assert CELL not in end_to_end["resume_s"]["workloads"]
    assert end_to_end["resume_s"]["bound"] == 0.075
    assert {m["name"]: m["bound"] for m in MANIFEST["end_to_end"] if m["name"] != "resume_s"} == {
        "setup_s": 0.1, "train_tokens_per_s": 0.1}
    from test_harness import one_chip_save_loop_cells, within_the_four_chip_quota

    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == "staged_wait_ms")
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": "staged_wait_ms", "unit": "ms", "better": "lower", "source": "host_clock",
        "layer": "entry", "moves": "train_tokens_per_s"}
    # This cell first; a later cell whose program donates is listed after it.
    assert entry["workloads"][0] == CELL
    assert set(entry["workloads"]) <= set(one_chip_save_loop_cells())
    # Appended: after every entry the manifest had at PR 34.
    names = [m["name"] for m in MANIFEST["per_layer"]]
    assert names.index("staged_wait_ms") > names.index("save_durable_s.one_chip")
    spec = harness.layer_metric_spec("staged_wait_ms")
    assert spec["reducer"] == "window_value" and spec["args"] == {"name": "staged_wait_ms"}
    assert not spec.get("count")  # a time: never printed from the CPU
    # The one cell whose state exists only across chips keeps its four; how
    # many may is a rule of the cells' number (``test_harness.py`` holds it).
    assert cells["pythia-1b.save-loop"]["chips"] == 4
    assert within_the_four_chip_quota(MANIFEST)
