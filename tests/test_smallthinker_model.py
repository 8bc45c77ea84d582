"""``tpusnap.models.SmallThinker`` against its plain reference
(``perf/reference/smallthinker.py``, which shares no code with it), at tiny
sizes on the CPU with seeded random weights: loss and every gradient leaf;
the eight expert shares added up against the uncut layer; the window and
the layers' positional encoding; no token dropped at the worst imbalance;
and the model's state through ``Snapshot.take`` / ``async_take`` /
``restore`` bit for bit, in more than one device-packed slab, with the
counters and the span that the pack emits."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from perf.reference import smallthinker as reference  # noqa: E402
from tpusnap import PytreeState, Snapshot, metrics_sink, telemetry  # noqa: E402
from tpusnap.models import SmallThinker, SmallThinkerConfig, make_mesh, make_train_step  # noqa: E402
from tpusnap.models.smallthinker import blocked_attention  # noqa: E402
from tpusnap.models.transformer import init_train_state  # noqa: E402

# A period of 4, a window shorter than the sequence, 8 experts routed top
# 2 of which 2 are held; blocks small enough that the sequence takes several.
TINY = SmallThinkerConfig(
    vocab_size=256, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, n_layers=4, d_expert=32,
    n_experts=8, top_k=2, first_expert=0, n_held_experts=2, window=12, rope_theta=10000.0,
    q_block=8, loss_block=16,
)
SEQ = 32


def sizes_of(cfg: SmallThinkerConfig):
    """The reference's sizes for a model configuration (the reference
    reads a configuration file's keys; the tests have none)."""
    return {
        "vocab": cfg.vocab_size, "d": cfg.d_model, "heads": cfg.n_heads,
        "kv_heads": cfg.n_kv_heads, "dh": cfg.head_dim, "layers": cfg.n_layers,
        "f": cfg.d_expert, "router": cfg.n_experts, "held": cfg.n_held_experts,
        "first": cfg.first_expert, "top_k": cfg.top_k, "window": cfg.window,
        "theta": cfg.rope_theta, "eps": 1e-6, "rope": cfg.rope_layout[:cfg.n_layers],
        "windowed": cfg.window_layout[:cfg.n_layers],
    }


def tokens(seed=0, batch=2, seq=SEQ, vocab=TINY.vocab_size):
    return jnp.asarray(np.random.default_rng(seed).integers(0, vocab, (batch, seq)), jnp.int32)


def leaf_gaps(got, want):
    """Norm of the difference over the reference's norm, by leaf path."""
    flat = jax.tree_util.tree_flatten_with_path(
        jax.tree.map(lambda g, w: float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w)), got, want)
    )[0]
    return {"/".join(str(k.key) for k in path): gap for path, gap in flat}


@pytest.fixture(scope="module")
def highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.mark.parametrize("dtype,loss_tol,leaf_tol", [
    (jnp.float32, 1e-6, 1e-4),  # the same mathematics
    (jnp.bfloat16, 5e-3, 0.35),  # the configuration's arithmetic: a top-k choice may flip
], ids=["float32", "bfloat16"])
def test_loss_and_every_gradient_leaf_match_the_reference(highest, dtype, loss_tol, leaf_tol):
    model = SmallThinker(dataclasses.replace(TINY, dtype=dtype))
    sizes = sizes_of(TINY)
    key = jax.random.PRNGKey(3)
    params, ref_params = model.init(key), reference.init_params(key, sizes)
    # Both make the same weights from the seed, neither taking the other's.
    assert jax.tree.structure(params) == jax.tree.structure(ref_params)
    assert all(bool((a == b).all()) for a, b in zip(
        jax.tree.leaves(params), jax.tree.leaves(ref_params)))
    batch = tokens()
    loss, grads = jax.jit(jax.value_and_grad(model.loss))(params, batch)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p, t: reference.loss_fn(p, t, sizes, None)))(ref_params, batch)
    assert abs(float(loss) - float(want)) <= loss_tol * float(want)
    gaps = leaf_gaps(grads, want_grads)
    assert len(gaps) == 43 and max(gaps.values()) <= leaf_tol, sorted(
        gaps.items(), key=lambda kv: -kv[1])[:5]


@pytest.mark.parametrize("held", [1, 2, 4])
def test_the_shares_of_the_expert_layer_add_up_to_the_uncut_layer(highest, held):
    """``8 / held`` chips, each told which ``held`` of the 8 experts it
    holds and handed those experts' slices of the banks: their outputs,
    added up, are the uncut reference's expert layer (what every chip
    computes alike, the router, enters each share and is counted once in
    the sum because it is no addend)."""
    uncut = dict(sizes_of(TINY), held=8, first=0)
    layer = reference.init_params(jax.random.PRNGKey(5), uncut)["layers"]["01"]
    key_a, key_b = jax.random.split(jax.random.PRNGKey(6))
    a = jax.random.normal(key_a, (2, SEQ, TINY.d_model), jnp.float32)
    b = jax.random.normal(key_b, (2, SEQ, TINY.d_model), jnp.float32)
    want = reference.experts(layer, a, b, uncut)
    total = jnp.zeros_like(want)
    for first in range(0, 8, held):
        share = SmallThinker(dataclasses.replace(
            TINY, dtype=jnp.float32, first_expert=first, n_held_experts=held))
        banks = {k: layer[k][first:first + held] for k in ("w_gate", "w_up", "w_down")}
        part = jax.jit(share.experts)({**layer, **banks}, a, b)
        # A share alone is the reference told of the same share.
        alone = reference.experts({**layer, **banks}, a, b, dict(uncut, held=held, first=first))
        np.testing.assert_allclose(part, alone, rtol=1e-4, atol=1e-5)
        total = total + part
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)
    assert float(jnp.abs(want).max()) > 0.1


def test_no_token_is_dropped_when_every_token_goes_to_one_held_expert(highest):
    """The worst imbalance: every token's first choice is held expert 1
    and its second held expert 0, so all ``tokens x top_k`` pairs are
    routed here."""
    model = SmallThinker(dataclasses.replace(TINY, dtype=jnp.float32))
    sizes = sizes_of(TINY)
    layer = reference.init_params(jax.random.PRNGKey(7), sizes)["layers"]["00"]
    router = jnp.zeros_like(layer["router"]).at[:, 1].set(1.0).at[:, 0].set(0.5)
    layer = {**layer, "router": router}
    a = jnp.abs(jax.random.normal(jax.random.PRNGKey(8), (2, SEQ, TINY.d_model))) + 0.1
    b = jax.random.normal(jax.random.PRNGKey(9), (2, SEQ, TINY.d_model))
    got = jax.jit(model.experts)(layer, a, b)
    np.testing.assert_allclose(got, reference.experts(layer, a, b, sizes), rtol=1e-4, atol=1e-5)
    # Every token got both experts' products: none of its rows is zero.
    assert float(jnp.abs(got).sum(-1).min()) > 0
    # And with nothing routed here, nothing comes out.
    nowhere = {**layer, "router": jnp.zeros_like(router).at[:, 6].set(1.0).at[:, 7].set(0.5)}
    assert float(jnp.abs(jax.jit(model.experts)(nowhere, a, b)).max()) == 0


def test_the_window_changes_the_loss_of_a_sequence_longer_than_it(highest):
    model = SmallThinker(dataclasses.replace(TINY, dtype=jnp.float32))
    no_window = SmallThinker(dataclasses.replace(
        TINY, dtype=jnp.float32, window_layout=(0, 0, 0, 0)))
    wide = SmallThinker(dataclasses.replace(TINY, dtype=jnp.float32, window=SEQ))
    params = model.init(jax.random.PRNGKey(11))
    batch = tokens(4)
    loss, off, covered = (float(jax.jit(m.loss)(params, batch)) for m in (model, no_window, wide))
    assert abs(loss - off) > 1e-4 * off
    # A window that covers the whole sequence is no window.
    assert covered == pytest.approx(off, rel=1e-6)
    # Up to the window's length the two agree position for position.
    short = tokens(4, seq=TINY.window)
    assert float(jax.jit(model.loss)(params, short)) == pytest.approx(
        float(jax.jit(no_window.loss)(params, short)), rel=1e-6)


def test_the_global_layer_ignores_the_order_of_its_keys_and_the_rope_layers_do_not(highest):
    """Layer 0 has no positional encoding: the last query's output is the
    same when two earlier positions change places. Layer 1 rotates q and
    k: there the same swap moves it."""
    model = SmallThinker(dataclasses.replace(TINY, dtype=jnp.float32, window=SEQ))
    layer = model.init(jax.random.PRNGKey(13))["layers"]["00"]
    a = jax.random.normal(jax.random.PRNGKey(14), (1, SEQ, TINY.d_model))
    swapped = a.at[:, 3].set(a[:, 20]).at[:, 20].set(a[:, 3])
    last = lambda x, index: jax.jit(  # noqa: E731
        model._attention, static_argnums=(2,))(layer, x, index)[:, -1]
    np.testing.assert_allclose(last(a, 0), last(swapped, 0), rtol=1e-5, atol=1e-6)
    assert float(jnp.abs(last(a, 1) - last(swapped, 1)).max()) > 1e-3


@pytest.mark.parametrize("window", [None, 5, 12])
def test_blocked_attention_is_the_references_attention(highest, window):
    keys = jax.random.split(jax.random.PRNGKey(15), 3)
    q = jax.random.normal(keys[0], (2, SEQ, 4, 16))
    k, v = (jax.random.normal(key, (2, SEQ, 2, 16)) for key in keys[1:])
    got = blocked_attention(q, k, v, window=window, q_block=8)
    np.testing.assert_allclose(got, reference.attention(q, k, v, window), rtol=1e-5, atol=1e-5)


def test_routed_rows_only_are_multiplied():
    """The step holds three grouped products (``ragged_dot``) a layer over
    the ``tokens x top_k`` sorted token-expert rows, so that no pair is
    dropped, and no scatter between tokens and pairs, forward or backward:
    both ways are gathers. (How a grouped product is lowered is the
    backend's: the TPU compiler makes it one kernel that skips the rows
    past the last group, the CPU's expands it.)"""
    model = SmallThinker(TINY)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    forward = str(jax.make_jaxpr(model.loss)(params, tokens()))
    assert forward.count("ragged_dot_general[") == 3 * TINY.n_layers
    pairs = 2 * SEQ * TINY.top_k
    assert f"bf16[{pairs},{TINY.d_model}]" in forward
    layer = params["layers"]["01"]
    a = jax.ShapeDtypeStruct((2, SEQ, TINY.d_model), jnp.float32)
    b = jax.ShapeDtypeStruct((2, SEQ, TINY.d_model), jnp.bfloat16)
    backward = str(jax.make_jaxpr(jax.grad(
        lambda lp, a, b: model.experts(lp, a, b).sum(), argnums=(0, 2)))(layer, a, b))
    wide_scatters = [ln for ln in backward.splitlines()
                     if "scatter" in ln and f",{TINY.d_model}]" in ln.split("=")[0]]
    assert not wide_scatters, wide_scatters[:3]


# ---- the state on tpusnap's own path


class Sink:
    def __init__(self):
        self.spans, self.counters = [], {}

    def on_span_record(self, record):
        self.spans.append(record)

    def on_counter(self, name, delta, value):
        self.counters[name] = self.counters.get(name, 0) + delta

    def __getattr__(self, name):
        if name.startswith("on_"):
            return lambda *a, **k: None
        raise AttributeError(name)


@pytest.fixture()
def trained_state():
    model = SmallThinker(TINY)
    mesh = make_mesh(jax.devices()[:1], (1, 1, 1))
    state = init_train_state(model, mesh, jax.random.PRNGKey(17))
    state, loss = make_train_step(model, mesh)(state, tokens(2))
    assert np.isfinite(float(loss))
    return state


@pytest.mark.parametrize("how", ["take", "async_take"])
def test_the_state_round_trips_bit_for_bit_through_device_packed_slabs(
        tmp_path, monkeypatch, trained_state, how):
    """130 leaves' worth of structure at the tiny size (43 a tree, three
    trees and the step), in several slabs packed on the device: restored
    bit for bit, no pack fell back to the host, and the pack says what it
    packed: ``batcher.device_slabs``, ``batcher.device_slab_bytes`` and one
    ``slab.pack`` span of kind work a slab."""
    monkeypatch.setenv("TPUSNAP_TELEMETRY", "1")
    monkeypatch.setenv("TPUSNAP_SLAB_SIZE_THRESHOLD_BYTES", str(48 << 10))
    assert len(jax.tree.leaves(trained_state)) == 130
    telemetry.reset_global_counters()
    path = str(tmp_path / "snap")
    with metrics_sink(Sink()) as sink:
        if how == "take":
            Snapshot.take(path, {"train": PytreeState(trained_state)})
        else:
            Snapshot.async_take(path, {"train": PytreeState(trained_state)}).wait()
    targets = {"train": PytreeState(jax.tree.map(jnp.zeros_like, trained_state))}
    Snapshot(path).restore(targets)
    for want, got in zip(jax.tree.leaves(trained_state), jax.tree.leaves(targets["train"].tree)):
        assert got.dtype == want.dtype and got.shape == want.shape
        assert np.array_equal(np.asarray(want).reshape(-1).view(np.uint8),
                              np.asarray(got).reshape(-1).view(np.uint8))
    assert telemetry.counter_value("batcher.device_pack_fallbacks") == 0
    packs = [r for r in sink.spans if r.name == "slab.pack"]
    assert len(packs) > 1 and {r.kind for r in packs} == {telemetry.WORK}
    assert sink.counters["batcher.device_slabs"] == len(packs)
    assert sink.counters["batcher.device_slab_bytes"] == sum(r.attrs["bytes"] for r in packs)
    # Every slab is fetched after it is packed: its dtoh span starts where the pack ends.
    fetched = [r for r in sink.spans if r.name == "dtoh" and r.attrs.get("slab_members")]
    assert len(fetched) == len(packs)
    # Slab members are leaves under the threshold, whole: the embedding,
    # the head and their moments (64 KiB each) stay out.
    state_bytes = sum(x.nbytes for x in jax.tree.leaves(trained_state))
    assert 0 < sink.counters["batcher.device_slab_bytes"] <= state_bytes - 6 * 256 * 64 * 4
