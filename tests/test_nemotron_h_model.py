"""``tpusnap.models.NemotronH`` against its plain reference
(``perf/reference/nemotron_h.py``, which shares no code with it and whose
mixer is the recurrence over positions), at tiny sizes on the CPU with
seeded random weights: loss and every gradient leaf; the mixer outputs of
all head shares add up to the uncut reference's mixer, and the routed parts
of all expert shares, with the shared expert counted once, to its expert
layer; the correction bias changes which experts are chosen and not their
weights, and neither it nor its Adam moments move in a step; the
convolution is causal; attention carries no position term; what the
comparison tells apart; which of the state's largest leaves comes first; and
the whole train state, its 8-element and 3-D leaves among them, through
``take`` / ``restore`` bit for bit under a step that donates."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from perf.reference import nemotron_h as reference  # noqa: E402
from tpusnap import PytreeState, Snapshot  # noqa: E402
from tpusnap.models import NemotronH, NemotronHConfig, make_mesh, make_train_step  # noqa: E402
from tpusnap.models import nemotron_h as module  # noqa: E402
from tpusnap.models.transformer import init_train_state  # noqa: E402

# All three kinds of layer, the Mamba-2 mixer twice; 2 heads of one group
# held; 16 router outputs, top 4, experts 4-7 held; 4 query heads on 1 KV
# head; chunks and blocks small enough that the sequence takes several, and
# a sequence that is no multiple of the scan's chunk.
TINY = NemotronHConfig(vocab_size=256, d_model=64, pattern="EM*M", ssm_heads=2, ssm_head_dim=8,
                       ssm_groups=1, ssm_state=16, chunk=12, d_expert=24, d_shared=48,
                       n_experts=16, top_k=4, first_expert=4, n_held_experts=4, n_heads=4,
                       n_kv_heads=1, head_dim=16, q_block=8, loss_block=16)
SEQ = 32
LEAVES = 33  # counted out in ``test_both_make_the_same_weights_from_the_seed``
# Norm of a leaf's difference over the reference's norm of that leaf.
F32_LOSS, F32_LEAF = 1e-6, 2e-4  # the same mathematics, another order of sums
# bf16 operands, float32 accumulation: 2^-8 a product, four layers deep; a
# top-4 choice of 16 flips on a near tie, which gives or takes a held
# expert's token (the router and the banks then read tenths, as the latent
# model's); and ``A_log``, two elements summed over every position of
# thrice-rounded products of a chunk, reads 0.05.
BF16_LOSS, BF16_LEAF = 5e-3, 0.35


def sizes_of(cfg: NemotronHConfig):
    """The reference's sizes for a model configuration (the reference reads
    a configuration file's keys; the tests have none)."""
    return {"vocab": cfg.vocab_size, "d": cfg.d_model, "pattern": cfg.pattern,
            "ssm_heads": cfg.ssm_heads, "ssm_p": cfg.ssm_head_dim, "ssm_groups": cfg.ssm_groups,
            "ssm_n": cfg.ssm_state, "conv": cfg.conv_kernel, "dt_min": cfg.dt_min,
            "dt_max": cfg.dt_max, "dt_floor": cfg.dt_floor, "f": cfg.d_expert,
            "f_shared": cfg.d_shared, "router": cfg.n_experts, "held": cfg.n_held_experts,
            "first": cfg.first_expert, "top_k": cfg.top_k, "scale": cfg.routed_scale,
            "heads": cfg.n_heads, "kv_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
            "eps": cfg.norm_eps}


def tokens(seed=0, batch=2, seq=SEQ, vocab=TINY.vocab_size):
    return jnp.asarray(np.random.default_rng(seed).integers(0, vocab, (batch, seq)), jnp.int32)


def seeded(cfg=TINY, seed=3):
    """Weights with every norm's scale and every skip ``D`` off its starting
    value, so that a scale left out shows."""
    params = NemotronH(cfg).init(jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))

    def stir(path, leaf):
        if str(path[-1].key).startswith("ln") or str(path[-1].key) == "D":
            return leaf + 0.2 * jax.random.normal(next(keys), leaf.shape)
        return leaf

    return jax.tree_util.tree_map_with_path(stir, params)


def leaf_gaps(got, want):
    def gap(g, w):
        norm = float(jnp.linalg.norm(w))
        return float(jnp.linalg.norm(g - w)) / norm if norm else float(jnp.linalg.norm(g))

    flat = jax.tree_util.tree_flatten_with_path(jax.tree.map(gap, got, want))[0]
    return {"/".join(str(k.key) for k in path): value for path, value in flat}


def reference_loss_and_grads(params, batch, cfg=TINY):
    return jax.jit(jax.value_and_grad(
        lambda p, t: reference.loss_fn(p, t, sizes_of(cfg), None)))(params, batch)


@pytest.fixture(scope="module")
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def test_both_make_the_same_weights_from_the_seed():
    key = jax.random.PRNGKey(3)
    params, ref_params = NemotronH(TINY).init(key), reference.init_params(key, sizes_of(TINY))
    assert jax.tree.structure(params) == jax.tree.structure(ref_params)
    assert all(bool((a == b).all()) for a, b in zip(
        jax.tree.leaves(params), jax.tree.leaves(ref_params)))
    # 9 leaves a Mamba-2 layer, 7 an expert layer, 5 an attention layer;
    # embedding, head and the final norm. Each layer holds its kind's alone.
    assert len(jax.tree.leaves(params)) == 2 * 9 + 7 + 5 + 3 == LEAVES
    layers = params["layers"]
    assert sorted(layers["00"]) == ["ln", "router", "router_bias", "shared_down", "shared_up",
                                    "w_down", "w_up"]
    assert sorted(layers["01"]) == sorted(layers["03"]) == [
        "A_log", "D", "conv_b", "conv_w", "dt_bias", "in_proj", "ln", "ln_gate", "out_proj"]
    assert sorted(layers["02"]) == ["ln", "wk", "wo", "wq", "wv"]
    # The mixer's leaves as the public checkpoint holds them: one input
    # projection for z, x, B, C and dt; a convolution of [channels, 1, taps].
    mixer = layers["01"]
    assert mixer["in_proj"].shape == (64, 16 + (16 + 2 * 16) + 2)
    assert mixer["conv_w"].shape == (48, 1, 4) and mixer["conv_b"].shape == (48,)
    assert mixer["A_log"].shape == mixer["D"].shape == mixer["dt_bias"].shape == (2,)
    # dt_bias is the inverse softplus of a dt in [time_step_min, time_step_max];
    # A = -exp(A_log) lies in [-16, -1]; D starts at 1.
    step = np.asarray(jax.nn.softplus(mixer["dt_bias"]))
    assert (step >= 1e-3 * 0.999).all() and (step <= 0.1 * 1.001).all()
    assert (np.asarray(jnp.exp(mixer["A_log"])) >= 1).all() and (
        np.asarray(jnp.exp(mixer["A_log"])) <= 16).all()
    assert bool((mixer["D"] == 1).all())
    # The biases are there, small and not zero.
    bias = layers["00"]["router_bias"]
    assert bias.shape == (16,) and 0 < float(jnp.abs(bias).max()) < 0.1


def test_the_published_sizes_count_what_the_issue_counts():
    """The configuration's defaults are the cell's: 406,439,112 parameters
    in 56 leaves, and the reference counts the same from the same sizes."""
    shapes = jax.eval_shape(NemotronH(NemotronHConfig()).init, jax.random.PRNGKey(0))
    leaves = jax.tree.leaves(shapes)
    assert len(leaves) == 56
    assert sum(int(np.prod(x.shape)) for x in leaves) == 406_439_112
    per_kind = {kind: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes["layers"][name]))
                for kind, name in (("E", "00"), ("M", "01"), ("*", "06"))}
    assert per_kind == {"M": 4_845_464, "E": 100_125_440, "*": 3_443_328}
    mixer, bank = shapes["layers"]["01"], shapes["layers"]["00"]
    assert mixer["in_proj"].shape == (2688, 1288) and mixer["conv_w"].shape == (768, 1, 4)
    assert mixer["A_log"].shape == (8,) and mixer["out_proj"].shape == (512, 2688)
    assert bank["w_up"].shape == (8, 2688, 1856) and bank["w_down"].shape == (8, 1856, 2688)
    assert bank["w_up"].shape[-1] % 128  # a leaf that a save turns on the host
    small = [x for x in leaves if x.size * 4 < 16 * 1024 * 1024]
    assert len(small) == 42  # a slab's members: 126 with both moments
    assert len([x for x in leaves if x.size == 8]) == 9  # 27 with both moments


@pytest.mark.parametrize("dtype,loss_tol,leaf_tol", [
    (jnp.float32, F32_LOSS, F32_LEAF), (jnp.bfloat16, BF16_LOSS, BF16_LEAF),
], ids=["float32", "bfloat16"])
def test_loss_and_every_gradient_leaf_match_the_reference(highest, dtype, loss_tol, leaf_tol):
    model = NemotronH(dataclasses.replace(TINY, dtype=dtype))
    params, batch = seeded(), tokens()
    loss, grads = jax.jit(jax.value_and_grad(model.loss))(params, batch)
    want, want_grads = reference_loss_and_grads(params, batch)
    assert abs(float(loss) - float(want)) <= loss_tol * float(want)
    gaps = leaf_gaps(grads, want_grads)
    assert len(gaps) == LEAVES and max(gaps.values()) <= leaf_tol, sorted(
        gaps.items(), key=lambda kv: -kv[1])[:5]
    # The correction bias's gradient is zero on both sides, exactly.
    for grad in (grads, want_grads):
        assert not float(jnp.abs(grad["layers"]["00"]["router_bias"]).max())


@pytest.mark.parametrize("held_heads,held_groups", [(2, 1), (4, 2), (8, 4)],
                         ids=["4_shares_of_2_heads_1_group", "2_shares_of_4_heads_2_groups",
                              "the_mixer_whole"])
def test_the_head_shares_of_the_mixer_add_up_to_the_uncut_mixer(highest, held_heads, held_groups):
    """An 8-head, 4-group mixer, and chips that each hold ``held_heads`` of
    its heads with their ``held_groups`` groups: each is handed its heads'
    and its groups' columns of ``W_in``, their channels of the convolution,
    their decays, their channels of the gated norm and their rows of
    ``W_out``; their outputs, added up, are the uncut reference's mixer."""
    H, G, P, N, D = 8, 4, 8, 16, 64
    whole = dict(sizes_of(TINY), ssm_heads=H, ssm_groups=G, pattern="M")
    lp = reference.init_params(jax.random.PRNGKey(5), whole)["layers"]["00"]
    lp = {**lp, "ln_gate": lp["ln_gate"] + 0.2 * jax.random.normal(jax.random.PRNGKey(6), (H * P,)),
          "D": lp["D"] + 0.2 * jax.random.normal(jax.random.PRNGKey(7), (H,))}
    u = jax.random.normal(jax.random.PRNGKey(8), (2, SEQ, D), jnp.float32)
    want = reference.mamba(u, lp, whole)
    # Where each part of W_in's columns and of the convolution's channels begins.
    z0, x0, b0, c0, dt0 = 0, H * P, 2 * H * P, 2 * H * P + G * N, 2 * H * P + 2 * G * N
    total = jnp.zeros_like(want)
    for share in range(H // held_heads):
        h0, g0 = share * held_heads, share * held_groups
        heads = np.arange(h0, h0 + held_heads)
        channels = np.arange(h0 * P, (h0 + held_heads) * P)
        states = np.arange(g0 * N, (g0 + held_groups) * N)
        columns = np.concatenate([z0 + channels, x0 + channels, b0 + states, c0 + states,
                                  dt0 + heads])
        conv = np.concatenate([channels, H * P + states, H * P + G * N + states])
        held = {"A_log": lp["A_log"][heads], "D": lp["D"][heads], "dt_bias": lp["dt_bias"][heads],
                "conv_b": lp["conv_b"][conv], "conv_w": lp["conv_w"][conv],
                "in_proj": lp["in_proj"][:, columns], "ln_gate": lp["ln_gate"][channels],
                "out_proj": lp["out_proj"][channels]}
        model = NemotronH(dataclasses.replace(
            TINY, dtype=jnp.float32, ssm_heads=held_heads, ssm_groups=held_groups))
        part = jax.jit(model.mamba)(held, u)
        # A share alone is the reference told of the same share.
        alone = reference.mamba(
            u, held, dict(whole, ssm_heads=held_heads, ssm_groups=held_groups))
        np.testing.assert_allclose(part, alone, rtol=1e-4, atol=1e-5)
        total = total + part
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)
    assert float(jnp.abs(want).max()) > 0.1


@pytest.mark.parametrize("held", [2, 4, 8])
def test_the_shares_of_the_expert_layer_add_up_to_the_uncut_layer(highest, held):
    """``16 / held`` chips, each told which ``held`` of the 16 experts it
    holds and handed those experts' slices of the banks: their routed
    parts, added up, and the shared expert, which every chip computes alike,
    counted once, are the uncut reference's expert layer."""
    uncut = dict(sizes_of(TINY), held=16, first=0)
    layer = reference.init_params(jax.random.PRNGKey(5), uncut)["layers"]["00"]
    u = jax.random.normal(jax.random.PRNGKey(6), (2, SEQ, TINY.d_model), jnp.float32)
    shared = reference.relu2_mlp(u, layer["shared_up"], layer["shared_down"])
    want = reference.experts(u, layer, uncut)
    np.testing.assert_allclose(want, shared + reference.routed(u, layer, uncut), rtol=1e-6)
    total = shared
    for first in range(0, 16, held):
        share = NemotronH(dataclasses.replace(
            TINY, dtype=jnp.float32, first_expert=first, n_held_experts=held))
        banks = {k: layer[k][first:first + held] for k in ("w_up", "w_down")}
        part = jax.jit(share.routed)({**layer, **banks}, u)
        # A share alone is the reference told of the same share.
        alone = reference.routed(u, {**layer, **banks}, dict(uncut, held=held, first=first))
        np.testing.assert_allclose(part, alone, rtol=1e-4, atol=1e-5)
        total = total + part
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)
    assert float(jnp.abs(want - shared).max()) > 0.1
    # The shared expert, as the model computes it, is the reference's; and the
    # whole layer, as the model runs it, is the shared expert beside a share.
    share = NemotronH(dataclasses.replace(TINY, dtype=jnp.float32))
    np.testing.assert_allclose(jax.jit(share.shared)(layer, u), shared, rtol=1e-4, atol=1e-5)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, SEQ, TINY.d_model), jnp.float32)
    held_layer = reference.init_params(jax.random.PRNGKey(5), sizes_of(TINY))["layers"]["00"]
    np.testing.assert_allclose(
        jax.jit(share._layer, static_argnums=2)(held_layer, x, "E"),
        reference.layer(x, held_layer, "E", sizes_of(TINY)), rtol=1e-4, atol=1e-4)


def test_the_bias_changes_the_choice_and_not_the_weights(highest):
    """The chosen experts are the top ``top_k`` of score plus bias; their
    weights are their scores without it, over their sum, times 2.5."""
    model = NemotronH(dataclasses.replace(TINY, dtype=jnp.float32))
    layer = reference.init_params(jax.random.PRNGKey(5), sizes_of(TINY))["layers"]["00"]
    u = jax.random.normal(jax.random.PRNGKey(8), (2 * SEQ, TINY.d_model), jnp.float32)
    chosen, weights = jax.jit(model.route)(layer, u)
    plain, plain_weights = jax.jit(model.route)(
        {**layer, "router_bias": jnp.zeros_like(layer["router_bias"])}, u)
    scores = jax.nn.sigmoid(u @ layer["router"])
    # Some token's choice differs from the plain top 4 of its scores ...
    differs = np.asarray(jnp.sort(chosen, -1) != jnp.sort(plain, -1)).any(-1)
    assert 0 < differs.sum() < len(differs)
    np.testing.assert_array_equal(
        np.sort(chosen, -1), np.sort(jax.lax.top_k(scores + layer["router_bias"], 4)[1], -1))
    # ... and every weight is the chosen score over the chosen scores' sum, times 2.5.
    picked = jnp.take_along_axis(scores, chosen, -1)
    np.testing.assert_allclose(weights, 2.5 * picked / picked.sum(-1, keepdims=True), rtol=1e-5)
    np.testing.assert_allclose(weights.sum(-1), 2.5, rtol=1e-5)
    # Where the choice is the same, so are the weights: the bias is in none.
    by_expert = lambda w, c: np.take_along_axis(np.asarray(w), np.argsort(c, -1), -1)  # noqa: E731
    np.testing.assert_allclose(by_expert(weights, chosen)[~differs],
                               by_expert(plain_weights, plain)[~differs], rtol=1e-6)
    # A bias that lifts experts 4 and 5 over all others puts them in every choice.
    lifted = {**layer, "router_bias": jnp.zeros(16).at[jnp.array([4, 5])].set(2.0)}
    assert bool((jnp.sort(jax.jit(model.route)(lifted, u)[0][:, :2], -1) == jnp.array([4, 5])).all())


def test_a_step_leaves_the_bias_and_its_moments_as_they_were():
    """Under the unedited ``make_train_step``: the gradient of a correction
    bias is exactly zero, so Adam's moments of it stay zero and the leaf
    keeps its bits, while its router and the mixers' 2-element leaves move."""
    model = NemotronH(TINY)
    mesh = make_mesh(jax.devices()[:1], (1, 1, 1))
    state = init_train_state(model, mesh, jax.random.PRNGKey(17))
    before = jax.tree.map(np.array, state["params"])
    step = make_train_step(model, mesh)
    for seed in (5, 6):
        state, _ = step(state, tokens(seed))
    now, was = state["params"]["layers"]["00"], before["layers"]["00"]
    assert np.array_equal(np.asarray(now["router_bias"]), was["router_bias"])
    assert not np.array_equal(np.asarray(now["router"]), was["router"])
    for moment in ("mu", "nu"):
        assert not float(jnp.abs(state["opt"][moment]["layers"]["00"]["router_bias"]).max())
        assert float(jnp.abs(state["opt"][moment]["layers"]["00"]["router"]).max()) > 0
    for leaf in ("A_log", "D", "dt_bias", "conv_w", "conv_b"):
        assert not np.array_equal(np.asarray(state["params"]["layers"]["01"][leaf]),
                                  before["layers"]["01"][leaf]), leaf


def test_the_convolution_is_causal_and_its_last_tap_is_on_the_current_position():
    """Position ``t``'s output does not move when ``t + 1`` and later
    change; it is ``b + sum_j w[:, 0, j] x_{t - 3 + j}``, written out."""
    w = jax.random.normal(jax.random.PRNGKey(1), (6, 1, 4))
    bias = jax.random.normal(jax.random.PRNGKey(2), (6,))
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 12, 6))
    out = module.causal_conv(x, w, bias)
    t = 7
    moved = module.causal_conv(x.at[:, t + 1:].add(1.0), w, bias)
    np.testing.assert_array_equal(np.asarray(out[:, :t + 1]), np.asarray(moved[:, :t + 1]))
    assert float(jnp.abs(out[:, t + 1] - moved[:, t + 1]).max()) > 0.01
    want = bias + sum(w[:, 0, j] * x[:, t - 3 + j] for j in range(4))
    np.testing.assert_allclose(out[:, t], want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(out[:, 0], bias + w[:, 0, 3] * x[:, 0], rtol=1e-5, atol=1e-6)
    # And through the mixer: the layer's output at t reads nothing after t.
    model = NemotronH(dataclasses.replace(TINY, dtype=jnp.float32))
    lp = seeded()["layers"]["01"]
    u = jax.random.normal(jax.random.PRNGKey(4), (2, SEQ, TINY.d_model))
    base, later = model.mamba(lp, u), model.mamba(lp, u.at[:, 20:].add(1.0))
    np.testing.assert_array_equal(np.asarray(base[:, :20]), np.asarray(later[:, :20]))
    assert float(jnp.abs(base[:, 20:] - later[:, 20:]).max()) > 0.01


@pytest.mark.parametrize("q_block", [4, 8, SEQ])
def test_attention_carries_no_position_term(highest, q_block):
    """The model's attention sublayer against one written out here, head by
    head: four query heads on the one KV head, scores over ``sqrt(head_dim)``
    under the causal mask, and nothing that knows a position: whatever the
    block. With the mask taken as given, moving every token of the prefix
    around moves a later query's output not at all."""
    cfg = dataclasses.replace(TINY, dtype=jnp.float32, q_block=q_block)
    lp = seeded(cfg)["layers"]["02"]
    a = jax.random.normal(jax.random.PRNGKey(9), (2, SEQ, cfg.d_model), jnp.float32)
    got = jax.jit(NemotronH(cfg).attention)(lp, a)
    k, v = a @ lp["wk"], a @ lp["wv"]
    mask = jnp.tril(jnp.ones((SEQ, SEQ), bool))
    out = []
    for head in range(cfg.n_heads):
        q = (a @ lp["wq"])[..., head * cfg.head_dim:(head + 1) * cfg.head_dim]
        scores = jnp.einsum("bqd,bkd->bqk", q, k) / np.sqrt(cfg.head_dim)
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), -1)
        out.append(jnp.einsum("bqk,bkd->bqd", probs, v))
    want = jnp.concatenate(out, -1) @ lp["wo"]
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    # And the reference's own, which shares nothing with either.
    np.testing.assert_allclose(reference.attention(a, lp, sizes_of(cfg)), want, rtol=2e-4, atol=2e-5)
    # No position term: the last query sees a set of keys, not a sequence.
    shuffled = jnp.concatenate([a[:, :SEQ - 1][:, ::-1], a[:, SEQ - 1:]], axis=1)
    np.testing.assert_allclose(jax.jit(NemotronH(cfg).attention)(lp, shuffled)[:, -1],
                               got[:, -1], rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("left_out", ["nothing", "shared_expert", "routed_scale", "skip_term",
                                      "bias_in_the_weights", "gate_after_the_norm",
                                      "conv_bias", "a_rotary_key"])
def test_a_term_left_out_differs_from_the_reference_by_more_than_the_tolerance(
        highest, monkeypatch, left_out):
    """What the float32 tolerances tell apart: the model with one term of
    its equations left out (or put in the wrong place) is not the
    reference's, by orders more than the tolerance."""
    cfg = dataclasses.replace(TINY, dtype=jnp.float32)
    params, batch = seeded(), tokens(2)
    if left_out == "shared_expert":
        real = NemotronH.shared
        monkeypatch.setattr(NemotronH, "shared", lambda self, lp, u: 0.0 * real(self, lp, u))
    elif left_out == "routed_scale":
        cfg = dataclasses.replace(cfg, routed_scale=1.0)
    elif left_out == "skip_term":
        params = jax.tree_util.tree_map_with_path(
            lambda path, leaf: jnp.zeros_like(leaf) if str(path[-1].key) == "D" else leaf, params)
    elif left_out == "bias_in_the_weights":
        real_route = NemotronH.route

        def route(self, lp, u):  # weights from the biased scores: the plain top-k's mistake
            chosen, _ = real_route(self, lp, u)
            scores = jax.nn.sigmoid(u @ lp["router"]) + lp["router_bias"]
            picked = jnp.take_along_axis(scores, chosen, -1)
            return chosen, 2.5 * picked / picked.sum(-1, keepdims=True)

        monkeypatch.setattr(NemotronH, "route", route)
    elif left_out == "gate_after_the_norm":
        # Mamba-2's other order (norm, then gate): silu(z) handed on as 1 and
        # multiplied in after the norm instead.
        real_silu = jax.nn.silu

        def mamba(self, lp, u):
            z = jnp.split(u @ lp["in_proj"], [self.config.ssm_width], axis=-1)[0]
            with monkeypatch.context() as m:
                m.setattr(jax.nn, "silu", lambda t: (
                    jnp.ones_like(t) if t.shape[-1] == self.config.ssm_width else real_silu(t)))
                out = real_mamba(self, {**lp, "out_proj": jnp.eye(self.config.ssm_width)}, u)
            return (out * real_silu(z)) @ lp["out_proj"]

        real_mamba = NemotronH.mamba
        monkeypatch.setattr(NemotronH, "mamba", mamba)
    elif left_out == "conv_bias":
        params = jax.tree_util.tree_map_with_path(
            lambda path, leaf: jnp.zeros_like(leaf) if str(path[-1].key) == "conv_b" else leaf,
            params)
    elif left_out == "a_rotary_key":
        from tpusnap.models.transformer import _rope
        real_blocked = module.blocked_attention
        monkeypatch.setattr(module, "blocked_attention", lambda q, k, v, **kw: real_blocked(
            _rope(q, 1e4), _rope(k, 1e4), v, **kw))
    loss, grads = jax.jit(jax.value_and_grad(NemotronH(cfg).loss))(params, batch)
    want, want_grads = reference_loss_and_grads(seeded(), batch)
    gaps = leaf_gaps(grads, want_grads)
    if left_out in ("skip_term", "conv_bias"):  # a leaf set to zero has its own gradient still
        gaps = {k: v for k, v in gaps.items() if not k.endswith(("/D", "/conv_b"))}
    worst = max(gaps.values())
    if left_out == "nothing":
        assert abs(float(loss) - float(want)) <= F32_LOSS * float(want) and worst <= F32_LEAF
    else:
        assert worst > 50 * F32_LEAF, (left_out, worst)


def test_the_first_of_the_largest_leaves_is_dense():
    """The state's two largest leaves tie, as published (the vocabulary's two
    matrices); of a tree's largest the codec's ``auto`` policy samples the
    first, and that is the head's moment, dense after one step, and not the
    embedding's, which is zero in every row whose token the job has not
    seen yet."""
    cfg = dataclasses.replace(TINY, vocab_size=1024)  # as published: the vocabulary's leaves lead
    model, batch = NemotronH(cfg), tokens(5, vocab=1024)
    mesh = make_mesh(jax.devices()[:1], (1, 1, 1))
    state = init_train_state(model, mesh, jax.random.PRNGKey(17))
    state, _ = make_train_step(model, mesh)(state, batch)
    flat = jax.tree_util.tree_flatten_with_path(state)[0]
    largest = max(flat, key=lambda kv: kv[1].nbytes)  # the first of equals, as the policy takes it
    assert "/".join(str(k.key) for k in largest[0]) == "opt/mu/decode"
    assert largest[1].nbytes == state["opt"]["mu"]["embed"].nbytes
    assert bool((jnp.abs(state["opt"]["mu"]["decode"]).sum(axis=0) > 0).all())
    rows = np.asarray(jnp.abs(state["opt"]["mu"]["embed"]).sum(axis=1) > 0)
    assert rows.sum() <= len(np.unique(np.asarray(batch))) < cfg.vocab_size


@pytest.mark.parametrize("how", ["take", "async_take"])
def test_the_donated_state_round_trips_bit_for_bit(tmp_path, monkeypatch, how):
    """A step compiled with ``donate_argnums=0``: 3 x 33 + 1 leaves, among
    them vectors of 2 elements (``A_log``, ``D``, ``dt_bias``: 8 bytes, 32
    at the cell's 8 heads), three-dimensional convolution leaves and banks
    whose minor dimension (24) is no tile's, most of them members of slabs
    packed on the device; the snapshot restores every leaf bit for bit, the
    bias's zero moments among them, and the step after the restore is the
    step the loop took."""
    monkeypatch.setenv("TPUSNAP_SLAB_SIZE_THRESHOLD_BYTES", str(48 << 10))
    model = NemotronH(TINY)
    mesh = make_mesh(jax.devices()[:1], (1, 1, 1))
    step = jax.jit(make_train_step(model, mesh), donate_argnums=0)
    state = init_train_state(model, mesh, jax.random.PRNGKey(17))
    state, loss = step(state, tokens(5))
    assert np.isfinite(float(loss))
    assert len(jax.tree.leaves(state)) == 3 * LEAVES + 1
    want = jax.tree.map(np.array, state)
    assert not want["opt"]["nu"]["layers"]["00"]["router_bias"].any()
    mixer = want["opt"]["mu"]["layers"]["01"]
    assert mixer["A_log"].shape == (2,) and mixer["A_log"].nbytes == 8 and mixer["A_log"].any()
    assert mixer["conv_w"].ndim == 3 and mixer["conv_w"].any()
    path = str(tmp_path / "snap")
    if how == "take":
        Snapshot.take(path, {"train": PytreeState(state)})
    else:
        pending = Snapshot.async_take(path, {"train": PytreeState(state)})
        assert pending.wait_staged(timeout=120)
    handed = state
    state, _ = step(state, tokens(6))
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(handed))
    if how == "async_take":
        pending.wait()
    targets = {"train": PytreeState(jax.tree.map(lambda x: jnp.ones(x.shape, x.dtype), want))}
    Snapshot(path).restore(targets)
    restored = targets["train"].tree
    assert jax.tree.structure(restored) == jax.tree.structure(want)
    for saved, got in zip(jax.tree.leaves(want), jax.tree.leaves(restored)):
        assert got.dtype == saved.dtype and got.shape == saved.shape
        assert np.array_equal(saved.reshape(-1).view(np.uint8),
                              np.asarray(got).reshape(-1).view(np.uint8))
    resumed, _ = step(restored, tokens(6))
    assert all(np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(jax.tree.leaves(resumed), jax.tree.leaves(state)))
