"""``tpusnap.models.Ouro`` against its plain reference
(``perf/reference/ouro.py``, which shares no code with it), at tiny sizes
on the CPU with seeded random weights: loss and every gradient leaf; one
pass without the entropy term is a plain decoder's cross-entropy; the
gradient of a leaf that every pass uses is the sum over the copies of an
unrolled model; the exit distribution; what the comparison tells apart (a
final norm or a post-sublayer norm left out); which of the state's largest
leaves comes first; and the whole train state,
the gate's one-element bias included, through ``async_take`` /
``wait_staged()`` / ``restore`` bit for bit under a step that donates."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from perf.reference import ouro as reference  # noqa: E402
from tpusnap import PytreeState, Snapshot  # noqa: E402
from tpusnap.models import Ouro, OuroConfig, make_mesh, make_train_step  # noqa: E402
from tpusnap.models.ouro import _rmsnorm  # noqa: E402
from tpusnap.models.transformer import init_train_state  # noqa: E402

# Three layers run four times; blocks small enough that the sequence takes several.
TINY = OuroConfig(vocab_size=256, d_model=64, n_heads=4, head_dim=16, n_layers=3, d_ff=96,
                  n_passes=4, rope_theta=1e6, entropy_weight=0.1, q_block=8, loss_block=16)
SEQ = 32
# Norm of a leaf's difference over the reference's norm of that leaf.
F32_LOSS, F32_LEAF = 1e-6, 2e-4  # the same mathematics
# bf16 operands, float32 accumulation: 2^-8 a product, 12 layer applications deep.
BF16_LOSS, BF16_LEAF = 5e-3, 0.08


def sizes_of(cfg: OuroConfig):
    """The reference's sizes for a model configuration (the reference
    reads a configuration file's keys; the tests have none)."""
    return {"vocab": cfg.vocab_size, "d": cfg.d_model, "heads": cfg.n_heads, "dh": cfg.head_dim,
            "layers": cfg.n_layers, "f": cfg.d_ff, "passes": cfg.n_passes,
            "theta": cfg.rope_theta, "eps": 1e-6, "beta": cfg.entropy_weight}


def tokens(seed=0, batch=2, seq=SEQ, vocab=TINY.vocab_size):
    return jnp.asarray(np.random.default_rng(seed).integers(0, vocab, (batch, seq)), jnp.int32)


def seeded(cfg=TINY, seed=3):
    """Weights with every norm's scale and the gate's bias off their
    starting values, so that a scale or a bias left out shows."""
    params = Ouro(cfg).init(jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))

    def stir(path, leaf):
        name = str(path[-1].key)
        if name.startswith("ln"):
            return leaf + 0.2 * jax.random.normal(next(keys), leaf.shape)
        if name == "b":
            return leaf + 0.3
        return leaf

    return jax.tree_util.tree_map_with_path(stir, params)


def leaf_gaps(got, want):
    flat = jax.tree_util.tree_flatten_with_path(
        jax.tree.map(lambda g, w: float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w)), got, want)
    )[0]
    return {"/".join(str(k.key) for k in path): gap for path, gap in flat}


def reference_loss_and_grads(params, batch, cfg=TINY):
    return jax.jit(jax.value_and_grad(
        lambda p, t: reference.loss_fn(p, t, sizes_of(cfg), None)))(params, batch)


@pytest.fixture(scope="module")
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def test_both_make_the_same_weights_from_the_seed():
    """Neither takes the other's: the same leaf paths, shapes and values."""
    key = jax.random.PRNGKey(5)
    params, ref_params = Ouro(TINY).init(key), reference.init_params(key, sizes_of(TINY))
    assert jax.tree.structure(params) == jax.tree.structure(ref_params)
    assert all(a.shape == b.shape and bool((a == b).all())
               for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(ref_params)))
    assert params["gate"]["b"].shape == (1,) and params["gate"]["w"].shape == (TINY.d_model,)
    assert set(params["layers"]) == {"ln1", "ln2", "ln3", "ln4", "wq", "wk", "wv", "wo",
                                     "w_gate", "w_up", "w_down"}
    assert all(leaf.shape[0] == TINY.n_layers for leaf in jax.tree.leaves(params["layers"]))


@pytest.mark.parametrize("dtype,loss_tol,leaf_tol", [
    (jnp.float32, F32_LOSS, F32_LEAF), (jnp.bfloat16, BF16_LOSS, BF16_LEAF),
], ids=["float32", "bfloat16"])
def test_loss_and_every_gradient_leaf_match_the_reference(highest, dtype, loss_tol, leaf_tol):
    model = Ouro(dataclasses.replace(TINY, dtype=dtype))
    params, batch = seeded(), tokens()
    loss, grads = jax.jit(jax.value_and_grad(model.loss))(params, batch)
    want, want_grads = reference_loss_and_grads(params, batch)
    assert abs(float(loss) - float(want)) <= loss_tol * float(want)
    gaps = leaf_gaps(grads, want_grads)
    assert len(gaps) == 16 and max(gaps.values()) <= leaf_tol, sorted(
        gaps.items(), key=lambda kv: -kv[1])[:5]


def test_one_pass_without_the_entropy_term_is_one_passes_cross_entropy(highest):
    """T = 1, beta = 0: the exit distribution is all on the one pass, and
    the loss is a plain sandwich-norm decoder's mean next-token
    cross-entropy, worked out here from the reference's layer."""
    cfg = dataclasses.replace(TINY, n_passes=1, entropy_weight=0.0, dtype=jnp.float32)
    params, batch, c = seeded(cfg), tokens(1), sizes_of(cfg)
    h = params["embed"][batch]
    for index in range(cfg.n_layers):
        h = reference.layer(h, jax.tree.map(lambda leaf: leaf[index], params["layers"]), c)
    x = reference._rmsnorm(h, params["ln_f"], 1e-6)
    logp = jax.nn.log_softmax(jnp.matmul(x[:, :-1], params["decode"]), axis=-1)
    want = -jnp.take_along_axis(logp, batch[:, 1:, None], axis=-1).mean()
    assert float(Ouro(cfg).loss(params, batch)) == pytest.approx(float(want), rel=F32_LOSS)
    assert float(reference.loss_fn(params, batch, c)) == pytest.approx(float(want), rel=F32_LOSS)
    # The gate decides nothing there: its gradient is exactly zero.
    grads = jax.grad(Ouro(cfg).loss)(params, batch)
    assert not float(jnp.abs(grads["gate"]["w"]).max()) and not float(grads["gate"]["b"][0])


def unrolled_loss(copies, rest, batch, c):
    """The reference's loss with a copy of the layers a pass: ``copies[t]``
    is the stack that pass ``t`` runs, from the reference's own pieces."""
    x = rest["embed"][batch]
    ces, logits = [], []
    for stack in copies:
        h = x
        for index in range(c["layers"]):
            h = reference.layer(h, jax.tree.map(lambda leaf: leaf[index], stack), c)
        x = reference._rmsnorm(h, rest["ln_f"], c["eps"])
        ces.append(reference.cross_entropy(x, rest["decode"], batch))
        logits.append((jnp.matmul(x, rest["gate"]["w"]) + rest["gate"]["b"])[:, :-1])
    terms = [jnp.exp(log_p) * (ce + c["beta"] * log_p)
             for log_p, ce in zip(reference.log_exit_distribution(logits), ces)]
    return sum(terms).mean()


def test_a_shared_leafs_gradient_is_the_sum_over_the_copies_of_an_unrolled_model(highest):
    """Four copies of the stack, one a pass, each with a gradient of its
    own: their sum is the gradient of the one stack that the looped model
    holds, in the reference and in the program; and no pass's part is
    zero, so a pass left out of the backward would show."""
    cfg = dataclasses.replace(TINY, dtype=jnp.float32)
    params, batch, c = seeded(cfg), tokens(2), sizes_of(cfg)
    rest = {k: v for k, v in params.items() if k != "layers"}
    copies = [params["layers"]] * cfg.n_passes
    by_copy = jax.jit(jax.grad(lambda cs: unrolled_loss(cs, rest, batch, c)))(copies)
    assert len(by_copy) == 4
    summed = jax.tree.map(lambda *g: sum(g), *by_copy)
    for name, part in ((f"pass {t}", g) for t, g in enumerate(by_copy)):
        assert all(float(jnp.linalg.norm(leaf)) > 0 for leaf in jax.tree.leaves(part)), name
    _, want = reference_loss_and_grads(params, batch, cfg)
    got = jax.jit(jax.grad(Ouro(cfg).loss))(params, batch)
    assert max(leaf_gaps(want["layers"], summed).values()) <= F32_LEAF
    assert max(leaf_gaps(got["layers"], summed).values()) <= F32_LEAF
    # One copy's gradient alone is not the shared leaf's.
    assert min(leaf_gaps(by_copy[-1], summed).values()) > 0.1


def test_the_exit_distribution_sums_to_one_and_beta_moves_the_loss(highest):
    z = [2.0 * jax.random.normal(jax.random.PRNGKey(t), (2, 7)) for t in range(4)]
    gates = [jax.nn.sigmoid(x) for x in z]
    p = [jnp.exp(x) for x in reference.log_exit_distribution(z)]
    assert len(p) == 4 and all(bool((x > 0).all()) for x in p)
    np.testing.assert_allclose(np.asarray(sum(p)), 1.0, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(p[1]), np.asarray(gates[1] * (1 - gates[0])), rtol=1e-5)
    # The last pass takes what is left, whatever its own gate says.
    np.testing.assert_allclose(np.asarray(p[3]), np.asarray(
        (1 - gates[0]) * (1 - gates[1]) * (1 - gates[2])), rtol=1e-5)
    # A saturated gate: p log p is 0 there, in the reference and in the program, not 0 * log 0.
    shut = reference.log_exit_distribution([jnp.full((1, 1), 200.0)] * 4)
    assert all(bool(jnp.isfinite(jnp.exp(x) * x).all()) for x in shut)
    assert float(jnp.exp(shut[0])[0, 0]) == 1.0 and float(jnp.exp(shut[3])[0, 0]) == 0.0
    # loss(beta) = loss(0) - beta * mean H(p): the same H in the program and the reference.
    params, batch = seeded(), tokens(3)
    cfg = dataclasses.replace(TINY, dtype=jnp.float32)
    at = lambda beta: float(Ouro(dataclasses.replace(cfg, entropy_weight=beta)).loss(  # noqa: E731
        params, batch))
    ref_at = lambda beta: float(reference.loss_fn(  # noqa: E731
        params, batch, {**sizes_of(cfg), "beta": beta}))
    entropy = (at(0.0) - at(0.1)) / 0.1
    assert 0.5 < entropy < np.log(4) + 1e-6  # four outcomes: at most log 4
    assert (ref_at(0.0) - ref_at(0.1)) / 0.1 == pytest.approx(entropy, rel=1e-4)
    assert at(0.2) == pytest.approx(at(0.0) - 0.2 * entropy, rel=1e-5)


def composed_loss(model, params, batch, norm_between_passes=True, post_norms=True):
    """The program's loss put together here from the program's own methods,
    with a switch for each norm that a port of a looped model forgets: the
    final norm between passes (the next pass then reads the stack's raw
    output; head and gate still read the normed one) and the norm after
    each sublayer."""
    cfg = model.config

    def layer(lp, h):
        if post_norms:
            return model._layer(lp, h)
        a = h + model._attention(lp, _rmsnorm(h, lp["ln1"]).astype(cfg.dtype))
        return a + model._mlp(lp, _rmsnorm(a, lp["ln3"]).astype(cfg.dtype))

    x = params["embed"][batch].astype(jnp.float32)
    stay, total = jnp.ones(batch.shape, jnp.float32), 0.0
    for t in range(cfg.n_passes):
        h, _ = lax.scan(lambda h, lp: (layer(lp, h), None), x, params["layers"])
        normed = _rmsnorm(h, params["ln_f"])
        ce = model._blocked_nll(normed.astype(cfg.dtype), params["decode"], batch)
        lam = jax.nn.sigmoid(normed @ params["gate"]["w"] + params["gate"]["b"])
        p = stay if t == cfg.n_passes - 1 else lam * stay
        total = total + p * (ce + cfg.entropy_weight * jnp.log(p))
        stay = stay * (1 - lam)
        x = normed if norm_between_passes else h
    return jnp.sum(total[:, :-1]) / (batch.shape[0] * (batch.shape[1] - 1))


@pytest.mark.parametrize("left_out", [{}, {"norm_between_passes": False}, {"post_norms": False}],
                         ids=["sound", "final_norm_between_passes", "post_sublayer_norms"])
def test_a_norm_left_out_differs_from_the_reference_by_more_than_the_tolerance(
        highest, left_out):
    """In the configuration's own arithmetic (bf16): put together whole, the
    composition is the model; with one kind of norm left out the gradients
    leave the tolerance that the sound model keeps, by far (the loss of
    random weights stays near log(vocabulary) whatever the layers do, so it
    is the gradients that tell)."""
    model = Ouro(TINY)
    params, batch = seeded(), tokens(4)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: composed_loss(model, p, batch, **left_out)))(params)
    want, want_grads = reference_loss_and_grads(params, batch)
    loss_gap = abs(float(loss) - float(want)) / float(want)
    worst = max(leaf_gaps(grads, want_grads).values())
    if not left_out:
        assert float(loss) == pytest.approx(float(model.loss(params, batch)), rel=1e-5)
        assert loss_gap <= BF16_LOSS and worst <= BF16_LEAF
    else:
        assert worst > 3 * BF16_LEAF, (loss_gap, worst)


def test_the_first_of_the_largest_leaves_is_dense():
    """Of a take's largest leaves the codec policy samples the one that
    comes first in the tree's order (``compress._sample_codec``). Here
    that is the head's first moment, which a step fills everywhere, and
    not the embedding's, which is zero in every row whose token the job
    has not seen yet: an 8 MiB sample of it would speak for the state."""
    cfg = dataclasses.replace(TINY, vocab_size=1024)  # as published: the vocabulary's leaves lead
    model, batch = Ouro(cfg), tokens(5, vocab=1024)
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


def test_the_donated_state_round_trips_bit_for_bit_with_its_one_element_leaf(tmp_path):
    """A step compiled with ``donate_argnums=0`` under a pending take: the
    take is handed the state, the loop waits until it is staged, the next
    step deletes what the take was handed, and the snapshot restores every
    leaf bit for bit: the gate's bias of one element and its two moments
    beside the stacked matrices."""
    model = Ouro(TINY)
    mesh = make_mesh(jax.devices()[:1], (1, 1, 1))
    step = jax.jit(make_train_step(model, mesh), donate_argnums=0)
    state = init_train_state(model, mesh, jax.random.PRNGKey(17))
    state, loss = step(state, tokens(5))
    assert np.isfinite(float(loss))
    assert len(jax.tree.leaves(state)) == 49  # 16 a tree, three trees and the step
    want = jax.tree.map(np.array, state)
    one_element = [want[k]["gate"]["b"] if k == "params" else want["opt"][k]["gate"]["b"]
                   for k in ("params", "mu", "nu")]
    assert all(leaf.shape == (1,) and leaf[0] != 0 for leaf in one_element)
    path = str(tmp_path / "snap")
    pending = Snapshot.async_take(path, {"train": PytreeState(state)})
    assert pending.wait_staged(timeout=120)
    handed = state
    state, _ = step(state, tokens(6))
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(handed))
    pending.wait()
    targets = {"train": PytreeState(jax.tree.map(lambda x: jnp.zeros(x.shape, x.dtype), want))}
    Snapshot(path).restore(targets)
    restored = targets["train"].tree
    assert jax.tree.structure(restored) == jax.tree.structure(want)
    for saved, got in zip(jax.tree.leaves(want), jax.tree.leaves(restored)):
        assert got.dtype == saved.dtype and got.shape == saved.shape
        assert np.array_equal(saved.reshape(-1).view(np.uint8),
                              np.asarray(got).reshape(-1).view(np.uint8))
    # The step after the restore is the step the loop took.
    resumed, _ = step(restored, tokens(6))
    assert all(np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(jax.tree.leaves(resumed), jax.tree.leaves(state)))
