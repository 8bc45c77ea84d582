"""``tpusnap.models.JoyAI`` against its plain reference
(``perf/reference/joyai.py``, which shares no code with it), at tiny sizes on
the CPU with seeded random weights: loss and every gradient leaf; the routed
parts of all the shares, with the shared expert counted once, add up to the
uncut reference's expert layer; the correction bias changes which experts
are chosen and not their weights, and neither it nor its Adam moments move
in a step; latent attention against a per-head attention written out on the
rebuilt keys and values; the multi-token-prediction term left out, and the
embedding's and the head's gradients as the sum over their two uses; what
the comparison tells apart; which of the state's largest leaves comes
first; and the whole train state through ``take`` / ``restore`` bit for bit
under a step that donates."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from perf.reference import joyai as reference  # noqa: E402
from tpusnap import PytreeState, Snapshot  # noqa: E402
from tpusnap.models import JoyAI, JoyAIConfig, make_mesh, make_train_step  # noqa: E402
from tpusnap.models import joyai as module  # noqa: E402
from tpusnap.models.transformer import init_train_state  # noqa: E402

# A dense layer and two expert layers, the module beside them; 16 router
# outputs, top 4, experts 4-7 held; blocks small enough that the sequence
# takes several.
TINY = JoyAIConfig(vocab_size=256, d_model=64, n_heads=2, q_rank=48, kv_rank=32, d_nope=16,
                   d_rope=8, d_v=16, n_layers=3, n_dense_layers=1, d_ff=160, d_expert=32,
                   n_experts=16, top_k=4, first_expert=4, n_held_experts=4, routed_scale=2.5,
                   n_mtp=1, mtp_weight=0.3, rope_theta=1e4, q_block=8, loss_block=16)
SEQ = 32
LEAVES = 70  # counted out in ``test_both_make_the_same_weights_from_the_seed``
# Norm of a leaf's difference over the reference's norm of that leaf.
F32_LOSS, F32_LEAF = 1e-6, 2e-4  # the same mathematics, another order of sums
# bf16 operands, float32 accumulation: 2^-8 a product, four blocks deep; and a
# top-4 choice of 16 flips on a near tie, which gives or takes a held
# expert's token: the routers and the banks then read tenths (0.09 on this
# seed, 0.13-0.33 on three others).
BF16_LOSS, BF16_LEAF = 5e-3, 0.35


def sizes_of(cfg: JoyAIConfig):
    """The reference's sizes for a model configuration (the reference reads
    a configuration file's keys; the tests have none)."""
    return {"vocab": cfg.vocab_size, "d": cfg.d_model, "heads": cfg.n_heads,
            "q_rank": cfg.q_rank, "kv_rank": cfg.kv_rank, "d_nope": cfg.d_nope,
            "d_rope": cfg.d_rope, "d_v": cfg.d_v, "layers": cfg.n_layers,
            "dense": cfg.n_dense_layers, "f_dense": cfg.d_ff, "f": cfg.d_expert,
            "router": cfg.n_experts, "held": cfg.n_held_experts, "first": cfg.first_expert,
            "top_k": cfg.top_k, "scale": cfg.routed_scale, "mtp": cfg.n_mtp,
            "lambda": cfg.mtp_weight, "theta": cfg.rope_theta, "eps": 1e-6}


def tokens(seed=0, batch=2, seq=SEQ, vocab=TINY.vocab_size):
    return jnp.asarray(np.random.default_rng(seed).integers(0, vocab, (batch, seq)), jnp.int32)


def seeded(cfg=TINY, seed=3):
    """Weights with every norm's scale off its starting value, so that a
    scale left out shows."""
    params = JoyAI(cfg).init(jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))

    def stir(path, leaf):
        if str(path[-1].key).startswith("ln"):
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
    params, ref_params = JoyAI(TINY).init(key), reference.init_params(key, sizes_of(TINY))
    assert jax.tree.structure(params) == jax.tree.structure(ref_params)
    assert all(bool((a == b).all()) for a, b in zip(
        jax.tree.leaves(params), jax.tree.leaves(ref_params)))
    # 9 leaves of attention and norms a block; 3 more in a dense layer, 8 in
    # an expert layer; the module's W_eh and three norms; embedding, head
    # and the final norm.
    assert len(jax.tree.leaves(params)) == 4 * 9 + 3 + 3 * 8 + 4 + 3 == LEAVES
    # The biases are there, small and not zero.
    bias = params["layers"]["01"]["router_bias"]
    assert bias.shape == (16,) and 0 < float(jnp.abs(bias).max()) < 0.1
    assert "router" not in params["layers"]["00"] and "mtp" not in JoyAI(
        dataclasses.replace(TINY, n_mtp=0)).init(key)


def test_the_published_sizes_count_what_the_issue_counts():
    """The configuration's defaults are the cell's: 376,091,904 parameters
    in 104 leaves, and the reference counts the same from the same sizes."""
    shapes = jax.eval_shape(JoyAI(JoyAIConfig()).init, jax.random.PRNGKey(0))
    leaves = jax.tree.leaves(shapes)
    assert len(leaves) == 104
    assert sum(int(np.prod(x.shape)) for x in leaves) == 376_091_904
    per_attention = (2048 * 1536 + 1536 + 1536 * 768 + 2048 * 576 + 512 + 512 * 1024
                     + 512 * 2048)
    assert per_attention == 7_079_936
    small = [x for x in leaves if x.size * 4 < 16 * 1024 * 1024]
    assert len(small) == 83  # a slab's members: 249 with both moments


@pytest.mark.parametrize("dtype,loss_tol,leaf_tol", [
    (jnp.float32, F32_LOSS, F32_LEAF), (jnp.bfloat16, BF16_LOSS, BF16_LEAF),
], ids=["float32", "bfloat16"])
def test_loss_and_every_gradient_leaf_match_the_reference(highest, dtype, loss_tol, leaf_tol):
    model = JoyAI(dataclasses.replace(TINY, dtype=dtype))
    params, batch = seeded(), tokens()
    loss, grads = jax.jit(jax.value_and_grad(model.loss))(params, batch)
    want, want_grads = reference_loss_and_grads(params, batch)
    assert abs(float(loss) - float(want)) <= loss_tol * float(want)
    gaps = leaf_gaps(grads, want_grads)
    assert len(gaps) == LEAVES and max(gaps.values()) <= leaf_tol, sorted(
        gaps.items(), key=lambda kv: -kv[1])[:5]
    # The correction biases' gradients are zero on both sides, exactly.
    for grad in (grads, want_grads):
        assert not float(jnp.abs(grad["layers"]["01"]["router_bias"]).max())
        assert not float(jnp.abs(grad["mtp"]["block"]["router_bias"]).max())


@pytest.mark.parametrize("held", [2, 4, 8])
def test_the_shares_of_the_expert_layer_add_up_to_the_uncut_layer(highest, held):
    """``16 / held`` chips, each told which ``held`` of the 16 experts it
    holds and handed those experts' slices of the banks: their routed
    parts, added up, and the shared expert, which every chip computes alike,
    counted once, are the uncut reference's expert layer."""
    uncut = dict(sizes_of(TINY), held=16, first=0)
    layer = reference.init_params(jax.random.PRNGKey(5), uncut)["layers"]["01"]
    u = jax.random.normal(jax.random.PRNGKey(6), (2, SEQ, TINY.d_model), jnp.float32)
    shared = reference.swiglu(u, layer["shared_gate"], layer["shared_up"], layer["shared_down"])
    want = shared + reference.routed(u, layer, uncut)
    total = shared
    for first in range(0, 16, held):
        share = JoyAI(dataclasses.replace(
            TINY, dtype=jnp.float32, first_expert=first, n_held_experts=held))
        banks = {k: layer[k][first:first + held] for k in ("w_gate", "w_up", "w_down")}
        part = jax.jit(share.routed)({**layer, **banks}, u)
        # A share alone is the reference told of the same share.
        alone = reference.routed(u, {**layer, **banks}, dict(uncut, held=held, first=first))
        np.testing.assert_allclose(part, alone, rtol=1e-4, atol=1e-5)
        total = total + part
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)
    assert float(jnp.abs(want - shared).max()) > 0.1
    # The whole layer, as the model runs it, is the shared expert beside a share.
    share = JoyAI(dataclasses.replace(TINY, dtype=jnp.float32))
    x = jax.random.normal(jax.random.PRNGKey(7), (2, SEQ, TINY.d_model), jnp.float32)
    held_layer = reference.init_params(jax.random.PRNGKey(5), sizes_of(TINY))["layers"]["01"]
    np.testing.assert_allclose(
        jax.jit(share._layer, static_argnums=2)(held_layer, x, None),
        reference.layer(x, held_layer, sizes_of(TINY)), rtol=1e-4, atol=1e-4)


def test_the_bias_changes_the_choice_and_not_the_weights(highest):
    """The chosen experts are the top ``top_k`` of score plus bias; their
    weights are their scores without it, over their sum, times 2.5."""
    model = JoyAI(dataclasses.replace(TINY, dtype=jnp.float32))
    layer = reference.init_params(jax.random.PRNGKey(5), sizes_of(TINY))["layers"]["01"]
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
    keeps its bits, while its router moves."""
    model = JoyAI(TINY)
    mesh = make_mesh(jax.devices()[:1], (1, 1, 1))
    state = init_train_state(model, mesh, jax.random.PRNGKey(17))
    before = jax.tree.map(np.array, state["params"])
    step = make_train_step(model, mesh)
    for seed in (5, 6):
        state, _ = step(state, tokens(seed))
    for block, was in (("01", before["layers"]["01"]), ("02", before["layers"]["02"])):
        now = state["params"]["layers"][block]
        assert np.array_equal(np.asarray(now["router_bias"]), was["router_bias"])
        assert not np.array_equal(np.asarray(now["router"]), was["router"])
        for moment in ("mu", "nu"):
            assert not float(jnp.abs(state["opt"][moment]["layers"][block]["router_bias"]).max())
            assert float(jnp.abs(state["opt"][moment]["layers"][block]["router"]).max()) > 0
    assert np.array_equal(np.asarray(state["params"]["mtp"]["block"]["router_bias"]),
                          before["mtp"]["block"]["router_bias"])


@pytest.mark.parametrize("q_block", [4, 8, SEQ])
def test_latent_attention_is_a_per_head_attention_on_the_rebuilt_keys_and_values(
        highest, q_block):
    """The model's attention sublayer against one written out here, head by
    head: queries from the query latent, every head's keys and values from
    the one key-value latent, the rotary part of the key shared by all
    heads, scores over ``sqrt(d_nope + d_rope)``; whatever the block."""
    cfg = dataclasses.replace(TINY, dtype=jnp.float32, q_block=q_block)
    lp = seeded(cfg)["layers"]["01"]
    a = jax.random.normal(jax.random.PRNGKey(9), (2, SEQ, cfg.d_model), jnp.float32)
    got = jax.jit(JoyAI(cfg)._attention)(lp, a)

    def rms(x, scale):
        return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * scale

    def rotate(x):  # [b, s, d]: channel 2i with 2i + 1, by position * theta^(-2i / d)
        d = x.shape[-1]
        angle = jnp.arange(SEQ)[:, None] * cfg.rope_theta ** (-jnp.arange(0, d, 2) / d)[None, :]
        even, odd = x[..., 0::2], x[..., 1::2]
        turned = jnp.stack([even * jnp.cos(angle) - odd * jnp.sin(angle),
                            even * jnp.sin(angle) + odd * jnp.cos(angle)], -1)
        return turned.reshape(x.shape)

    cq = rms(a @ lp["wq_a"], lp["ln_q"])
    kv = a @ lp["wkv_a"]
    latent, k_rope = rms(kv[..., :cfg.kv_rank], lp["ln_kv"]), rotate(kv[..., cfg.kv_rank:])
    d_qk, out = cfg.d_nope + cfg.d_rope, []
    mask = jnp.tril(jnp.ones((SEQ, SEQ), bool))
    for head in range(cfg.n_heads):
        q = (cq @ lp["wq_b"])[..., head * d_qk:(head + 1) * d_qk]
        q = jnp.concatenate([q[..., :cfg.d_nope], rotate(q[..., cfg.d_nope:])], -1)
        rebuilt = (latent @ lp["wkv_b"])[..., head * (cfg.d_nope + cfg.d_v):][
            ..., :cfg.d_nope + cfg.d_v]
        k = jnp.concatenate([rebuilt[..., :cfg.d_nope], k_rope], -1)
        scores = jnp.einsum("bqd,bkd->bqk", q, k) / np.sqrt(d_qk)
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), -1)
        out.append(jnp.einsum("bqk,bkd->bqd", probs, rebuilt[..., cfg.d_nope:]))
    want = jnp.concatenate(out, -1) @ lp["wo"]
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    # And the reference's own, which shares nothing with either.
    np.testing.assert_allclose(
        reference.latent_attention(a, lp, sizes_of(cfg)), want, rtol=2e-4, atol=2e-5)


def test_the_module_left_out_and_the_two_uses_of_the_embedding_and_the_head(highest):
    """``lambda = 0`` and no module at all give the main stack's
    cross-entropy alone; the loss is ``CE_main + lambda CE_mtp``; and the
    gradient of the embedding and of the head, which the module reads a
    second time, is the sum of the gradients through each use."""
    f32 = dataclasses.replace(TINY, dtype=jnp.float32)
    params, batch = seeded(), tokens(1)
    main_only = {k: v for k, v in params.items() if k != "mtp"}
    ce_main = jax.jit(JoyAI(dataclasses.replace(f32, n_mtp=0)).loss)(main_only, batch)
    muted = jax.jit(JoyAI(dataclasses.replace(f32, mtp_weight=0.0)).loss)(params, batch)
    assert float(ce_main) == pytest.approx(float(muted), rel=1e-6)
    # The reference with no module is the main stack's cross-entropy too.
    want = reference.loss_fn(main_only, batch, dict(sizes_of(f32), mtp=0), None)
    assert float(ce_main) == pytest.approx(float(want), rel=1e-5)
    whole = jax.jit(JoyAI(f32).loss)(params, batch)
    ce_mtp = (float(whole) - float(ce_main)) / 0.3
    assert 0.7 * float(ce_main) < ce_mtp < 1.3 * float(ce_main)  # random weights: log(vocab)
    doubled = jax.jit(JoyAI(dataclasses.replace(f32, mtp_weight=0.6)).loss)(params, batch)
    assert float(doubled) == pytest.approx(float(ce_main) + 0.6 * ce_mtp, rel=1e-5)

    # Two copies of the embedding and of the head, one for each use.
    model = JoyAI(f32)

    def unrolled(main, second):
        total = model._blocked_nll(
            model_hidden(main), main["decode"], batch, ahead=1)
        return total + 0.3 * model._mtp_nll(
            {**main, "embed": second["embed"], "decode": second["decode"]},
            model_hidden(main, final=True), batch)

    def model_hidden(p, final=False):
        x = jnp.take(p["embed"], batch, axis=0)
        for i in range(f32.n_layers):
            x = model._layer(p["layers"][f"{i:02d}"], x, None)
        h = module._rmsnorm(x, p["ln_f"])
        return h if final else h.astype(f32.dtype)

    second = {"embed": params["embed"], "decode": params["decode"]}
    assert float(jax.jit(unrolled)(params, second)) == pytest.approx(float(whole), rel=1e-6)
    first_use, second_use = jax.jit(jax.grad(unrolled, argnums=(0, 1)))(params, second)
    grads = jax.jit(jax.grad(model.loss))(params, batch)
    for leaf in ("embed", "decode"):
        assert float(jnp.linalg.norm(second_use[leaf])) > 0.05 * float(
            jnp.linalg.norm(first_use[leaf]))
        np.testing.assert_allclose(grads[leaf], first_use[leaf] + second_use[leaf],
                                   rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("left_out", ["nothing", "shared_expert", "routed_scale", "mtp_term",
                                      "bias_in_the_weights", "latent_norm"])
def test_a_term_left_out_differs_from_the_reference_by_more_than_the_tolerance(
        highest, monkeypatch, left_out):
    """What the float32 tolerances tell apart: the model with one term of
    its equations left out (or put in the wrong place) is not the
    reference's, by orders more than the tolerance."""
    cfg = dataclasses.replace(TINY, dtype=jnp.float32)
    params, batch = seeded(), tokens(2)
    if left_out == "shared_expert":
        real = module._swiglu
        monkeypatch.setattr(module, "_swiglu", lambda u, lp, gate, *rest: (
            0.0 * real(u, lp, gate, *rest) if gate == "shared_gate" else real(u, lp, gate, *rest)))
    elif left_out == "routed_scale":
        cfg = dataclasses.replace(cfg, routed_scale=1.0)
    elif left_out == "mtp_term":
        cfg = dataclasses.replace(cfg, mtp_weight=0.0)
    elif left_out == "bias_in_the_weights":
        real_route = JoyAI.route

        def route(self, lp, u):  # weights from the biased scores: the plain top-k's mistake
            chosen, _ = real_route(self, lp, u)
            scores = jax.nn.sigmoid(u @ lp["router"]) + lp["router_bias"]
            picked = jnp.take_along_axis(scores, chosen, -1)
            return chosen, 2.5 * picked / picked.sum(-1, keepdims=True)

        monkeypatch.setattr(JoyAI, "route", route)
    elif left_out == "latent_norm":
        params = jax.tree_util.tree_map_with_path(
            lambda path, leaf: jnp.ones_like(leaf) if str(path[-1].key) == "ln_kv" else leaf,
            params)
    loss, grads = jax.jit(jax.value_and_grad(JoyAI(cfg).loss))(params, batch)
    want, want_grads = reference_loss_and_grads(seeded(), batch)
    worst = max(leaf_gaps(grads, want_grads).values())
    if left_out == "nothing":
        assert abs(float(loss) - float(want)) <= F32_LOSS * float(want) and worst <= F32_LEAF
    else:
        assert worst > 50 * F32_LEAF, (left_out, worst)


def test_the_first_of_the_largest_leaves_is_dense():
    """The state's two largest leaves tie, as published (the vocabulary's two
    matrices); of a tree's largest the codec's ``auto`` policy samples the
    first, and that is the head's moment, dense after one step, and not the
    embedding's, which is zero in every row whose token the job has not
    seen yet (both uses of the embedding together see few of the rows)."""
    cfg = dataclasses.replace(TINY, vocab_size=1024)  # as published: the vocabulary's leaves lead
    model, batch = JoyAI(cfg), tokens(5, vocab=1024)
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
    """A step compiled with ``donate_argnums=0``: 3 x 70 + 1 leaves, vectors
    of a few hundred bytes (norm scales, the routers' biases) beside
    matrices, most of them members of slabs packed on the device; the
    snapshot restores every leaf bit for bit, the biases' zero moments
    among them, and the step after the restore is the step the loop took."""
    monkeypatch.setenv("TPUSNAP_SLAB_SIZE_THRESHOLD_BYTES", str(48 << 10))
    model = JoyAI(TINY)
    mesh = make_mesh(jax.devices()[:1], (1, 1, 1))
    step = jax.jit(make_train_step(model, mesh), donate_argnums=0)
    state = init_train_state(model, mesh, jax.random.PRNGKey(17))
    state, loss = step(state, tokens(5))
    assert np.isfinite(float(loss))
    assert len(jax.tree.leaves(state)) == 3 * LEAVES + 1
    want = jax.tree.map(np.array, state)
    assert not want["opt"]["nu"]["layers"]["01"]["router_bias"].any()
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
