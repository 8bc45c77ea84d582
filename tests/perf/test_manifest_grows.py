"""A sixth cell is data: a copy of the benchmark (``BENCHMARK.json``,
``perf/``, ``tests/perf/``) grown the way ``perf/README.md`` promises a
``model_config`` PR may grow it, by files added and entries appended and
by no edit to a file that is there: a configuration of another
architecture whose program donates its state, its rehearsal preset, a mix
of its own, one per-layer metric of its own, and a one-chip cell. The
benchmark's own shape tests (the marker ``manifest_shape``) then run over
the copy as they are, and the new cell rehearses. CPU only."""

import copy
import json
import os
import re
import shutil
import subprocess
import sys

import pytest
from test_harness import (
    MANIFEST, PERF, ROOT, SAVE_LOOP_CELL, STUB_CONFIG, STUB_PROGRAM, STUB_REFERENCE, _result,
    _run_in, within_the_four_chip_quota,
)

CONFIG, PRESET, MIX, METRIC = ("two-matrix-donated", "tiny-two-matrix-donated",
                               "save_loop_one", "staged_bytes_per_state_byte")
CELL = f"{CONFIG}.save-loop"
SHAPE_FILES = ("test_donation.py", "test_harness.py", "test_relayout_readings.py",
               "test_save_loop.py", "test_smallthinker.py", "test_span_metrics.py")

# ``two_matrix`` as a job runs it when the model fills the chip: what
# ``transformer_donated`` is to ``transformer``.
STUB_PROGRAM_DONATED = '''
import jax

from perf.programs import two_matrix


def build(config, devices, key):
    built = two_matrix.build(config, devices, key)
    built["train_step"] = jax.jit(
        built["train_step"], donate_argnums=0,
        out_shardings=(built["state_shardings"], built["token_sharding"]))
    built["donates"] = True
    return built
'''

NEW_FILES = {
    "perf/reference/two_matrix.py": STUB_REFERENCE,
    "perf/programs/two_matrix.py": STUB_PROGRAM.format(hidden="x + jnp.tanh(x)"),
    "perf/programs/two_matrix_donated.py": STUB_PROGRAM_DONATED,
    f"perf/configs/{CONFIG}.json": json.dumps({
        **STUB_CONFIG, "program": "two_matrix_donated", "vocab_size": 32768,
        "two_matrix_width": 4096, "mesh": [1], "reduced": {}, "rehearsal_config": PRESET,
        "assumed": {"batch": 1, "seq_len": 2048}}),
    f"perf/configs/{PRESET}.json": json.dumps({
        **STUB_CONFIG, "program": "two_matrix_donated", "mesh": [1]}),
    # One save a window: the next would be due after the window has closed.
    f"perf/traffic/{MIX}.json": json.dumps({
        "kind": "save_loop", "doc": "a stand-in: the mix save_loop at one save a window",
        "first_save_s": 10.0, "save_every_s": 60.0, "trace_lead_s": 0.5, "trace_max_s": 6.0,
        "rehearsal": {"first_save_s": 0.3, "save_every_s": 5.0, "trace_lead_s": 0.1,
                      "trace_max_s": 1.0},
        "env": {"TPUSNAP_DURABLE_COMMIT": "1"}}),
    f"perf/layer_metrics/{METRIC}.json": json.dumps({
        "reducer": "counted_bytes_per_state_byte",
        "args": {"counters": ["scheduler.bytes_staged"]},
        "doc": "a stand-in: bytes staged per byte of state, per save", "count": True}),
}


def _files(root):
    held = {}
    for folder, dirs, names in os.walk(root):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for name in names:
            path = os.path.join(folder, name)
            with open(path, "rb") as f:
                held[os.path.relpath(path, root)] = f.read()
    return held


def _grown(manifest):
    """``manifest`` with the new configuration, cell and metric appended."""
    grown = copy.deepcopy(manifest)
    grown["configs"].append(
        {"name": CONFIG, "source": STUB_CONFIG["source"], "file": f"perf/configs/{CONFIG}.json",
         "reduced": [], "why": "a stand-in: another architecture under a step that donates"})
    grown["workloads"].append(
        {"name": CELL, "config": CONFIG, "traffic": MIX, "chips": 1,
         "why": "a stand-in: closed train loop, one async_take a window, wait_staged() after it"})
    for metric in grown["end_to_end"] + grown["per_layer"]:
        # Wherever the first one-chip save loop is listed, and where a
        # program that donates is waited for.
        if "workloads" in metric and (SAVE_LOOP_CELL in metric["workloads"]
                                      or metric["name"] == "staged_wait_ms"):
            metric["workloads"].append(CELL)
    grown["per_layer"].append(
        {"name": METRIC, "unit": "ratio", "better": "lower", "source": "program_counter",
         "layer": "stage/hash", "moves": "train_tokens_per_s", "workloads": [CELL]})
    return grown


@pytest.fixture(scope="module")
def grown_checkout(tmp_path_factory):
    """The copy, what it held before it grew, and its grown manifest."""
    root = tmp_path_factory.mktemp("grown")
    unwanted = shutil.ignore_patterns("__pycache__")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root / "BENCHMARK.json")
    shutil.copytree(PERF, root / "perf", ignore=unwanted)
    shutil.copytree(os.path.join(ROOT, "tests", "perf"), root / "tests" / "perf", ignore=unwanted)
    before = _files(root)
    for path, text in NEW_FILES.items():
        assert path not in before, path
        (root / path).write_text(text)
    grown = _grown(MANIFEST)
    (root / "BENCHMARK.json").write_text(json.dumps(grown, indent=2) + "\n")
    return str(root), before, grown


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    return str(tmp_path_factory.mktemp("perf_jax_cache"))


def test_the_copys_own_shape_tests_pass_over_the_grown_manifest(grown_checkout, cache_dir):
    """Every ``manifest_shape`` test of the copy's six files, run as it is,
    and among them the cases that the new files and entries add."""
    root, _, _ = grown_checkout
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=8",  # as ``tests/conftest.py`` has it
        JAX_COMPILATION_CACHE_DIR=cache_dir,
        PYTHONPATH=ROOT,  # the library under test; ``perf`` and the tests are the copy's own
    )
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", os.path.join(root, "tests", "perf"), "-m",
         "manifest_shape", "-v", "--strict-markers", "--rootdir", root, "-p", "no:cacheprovider",
         "-p", "no:randomly"],
        capture_output=True, text=True, timeout=600, cwd=root, env=env)
    assert proc.returncode == 0, proc.stdout[-6000:] + proc.stderr[-3000:]
    passed = re.findall(r"^tests/perf/(\S+?)::(\S+) PASSED", proc.stdout, re.M)
    assert {name for name, _ in passed} == set(SHAPE_FILES)
    assert not re.search(r"\b(FAILED|ERROR|SKIPPED|XFAIL)\b", proc.stdout), proc.stdout[-6000:]
    cases = {case for _, case in passed}
    for wanted in (
        f"test_a_one_chip_save_loop_cell_is_listed_wherever_the_first_one_is[{CELL}]",
        f"test_the_window_holds_the_saves_that_the_mixs_file_gives[{MIX}]",
        f"test_the_program_keeps_its_contract_and_the_references_state_bytes[{PRESET}]",
        f"test_every_configuration_names_a_program_and_a_reference_that_exist[{CONFIG}.json]",
        "test_the_manifests_entries_for_the_donated_cell",
        "test_the_manifest_has_the_configuration_and_its_one_cell",
        "test_the_four_chip_cells_keep_to_their_quota",
    ):
        assert wanted in cases, wanted


def test_the_new_cell_rehearses_and_prints_its_own_reading(grown_checkout, cache_dir):
    root, _, grown = grown_checkout
    result = _result(_run_in(root, cache_dir, CONFIG, trace=1))
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] == 1
    assert result["checks"]["restored_bits_differ"] == {"value": 0, "limit": 0}
    listed = {m["name"] for m in grown["per_layer"] if CELL in m["workloads"]}
    assert set(result["metrics"]) <= listed
    assert result["metrics"][METRIC] == {"value": pytest.approx(1.0, abs=0.01), "unit": "ratio"}
    # The program donates: the loop waited for the take to be staged (a
    # time, so null off the chip), and every byte was written once.
    assert result["metrics"]["staged_wait_ms"] == {"value": None, "unit": "ms"}
    assert result["metrics"]["stored_bytes_per_state_byte"]["value"] == 1.0


def test_files_were_added_and_entries_appended_and_nothing_else(grown_checkout):
    """After the shape tests and the rehearsal have run in the copy: every
    file that was there is byte for byte the same but the manifest, and in
    the manifest every old entry stands in its old place, an old
    ``workloads`` list a prefix of the new one."""
    root, before, grown = grown_checkout
    after = _files(root)
    assert set(after) - set(before) == set(NEW_FILES)
    assert {p for p in before if before[p] != after.get(p)} == {"BENCHMARK.json"}
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        assert json.load(f) == grown
    for key, old in MANIFEST.items():
        if key not in ("configs", "workloads", "end_to_end", "per_layer"):
            assert grown[key] == old, key
            continue
        for was, now in zip(old, grown[key]):
            assert {**now, "workloads": None} == {**was, "workloads": None}
            listed = was.get("workloads", [])
            assert now.get("workloads", [])[:len(listed)] == listed, was["name"]
    assert [len(grown[k]) - len(MANIFEST[k]) for k in (
        "configs", "workloads", "end_to_end", "per_layer")] == [1, 1, 0, 1]


@pytest.mark.parametrize("cells, four_chip, taken", [
    (6, 1, True), (6, 2, False), (7, 2, False), (8, 2, True), (8, 3, False), (12, 3, True),
    (3, 1, True)])
def test_the_four_chip_quota_is_a_rule_of_the_cells_number(cells, four_chip, taken):
    """The function that the shape tests call, on the grown manifest's
    dictionary (no copy of the tree) with cells appended up to ``cells``,
    the first ``four_chip`` of them on four chips: a second four-chip cell is
    taken among eight cells and refused among six, whatever the manifest's
    own number."""
    grown = _grown(MANIFEST)
    held = grown["workloads"]
    padded = [*held, *({**held[-1], "name": f"appended-{k}"} for k in range(cells))][:cells]
    manifest = {**grown, "workloads": [
        {**cell, "chips": 4 if k < four_chip else 1} for k, cell in enumerate(padded)]}
    assert len(manifest["workloads"]) == cells
    assert within_the_four_chip_quota(manifest) is taken
    assert within_the_four_chip_quota(grown)
