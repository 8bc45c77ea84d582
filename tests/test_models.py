"""Tests for the flagship transformer + ring attention (models/, ops/).

Runs on the 8-device CPU mesh from conftest.py — the same environment
the driver uses to validate the multi-chip path.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from tpusnap.models import Transformer, TransformerConfig, make_mesh, make_train_step
from tpusnap.models.transformer import init_train_state, train_state_specs
from tpusnap.ops import ring_attention


def _dense_causal_attention(q, k, v):
    d = q.shape[-1]
    s = q.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * d**-0.5
    mask = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)


class TestRingAttention:
    def test_single_device_matches_dense(self):
        q, k, v = (
            jax.random.normal(kk, (2, 16, 4, 8), jnp.float32)
            for kk in jax.random.split(jax.random.PRNGKey(0), 3)
        )
        ref = _dense_causal_attention(q, k, v)
        out = ring_attention(q, k, v, axis_name=None, causal=True)
        np.testing.assert_allclose(out, ref, atol=1e-5)

    def test_ring_matches_dense_on_mesh(self):
        mesh = make_mesh()
        q, k, v = (
            jax.random.normal(kk, (2, 16, 4, 8), jnp.float32)
            for kk in jax.random.split(jax.random.PRNGKey(1), 3)
        )
        ref = _dense_causal_attention(q, k, v)
        spec = P("data", "fsdp", "tensor", None)
        fn = jax.jit(
            jax.shard_map(
                functools.partial(ring_attention, axis_name="fsdp", causal=True),
                mesh=mesh,
                in_specs=(spec, spec, spec),
                out_specs=spec,
            )
        )
        np.testing.assert_allclose(fn(q, k, v), ref, atol=1e-5)

    def test_non_causal(self):
        q, k, v = (
            jax.random.normal(kk, (1, 8, 2, 4), jnp.float32)
            for kk in jax.random.split(jax.random.PRNGKey(2), 3)
        )
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
        ref = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
        out = ring_attention(q, k, v, axis_name=None, causal=False)
        np.testing.assert_allclose(out, ref, atol=1e-5)

    def test_grads_flow(self):
        q, k, v = (
            jax.random.normal(kk, (1, 8, 2, 4), jnp.float32)
            for kk in jax.random.split(jax.random.PRNGKey(3), 3)
        )
        g = jax.grad(lambda q: ring_attention(q, k, v).sum())(q)
        assert bool(jnp.all(jnp.isfinite(g)))


_TINY = dict(vocab_size=128, d_model=64, n_heads=4, n_layers=2, d_ff=128)


class TestTransformer:
    def test_forward_shapes(self):
        model = Transformer(TransformerConfig(**_TINY))
        params = model.init(jax.random.PRNGKey(0))
        tokens = jnp.zeros((2, 16), jnp.int32)
        logits = jax.jit(model.apply)(params, tokens)
        assert logits.shape == (2, 16, 128)
        assert logits.dtype == jnp.float32

    @pytest.mark.parametrize("n_experts", [0, 4], ids=["dense", "moe"])
    @pytest.mark.parametrize("ring", [False, True], ids=["noring", "ring"])
    def test_train_step_decreases_loss(self, n_experts, ring):
        mesh = make_mesh()
        cfg = TransformerConfig(
            **_TINY, n_experts=n_experts, use_ring_attention=ring
        )
        model = Transformer(cfg)
        state = init_train_state(model, mesh, jax.random.PRNGKey(0))
        train_step = make_train_step(model, mesh, learning_rate=1e-2)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, 128)
        losses = []
        for _ in range(3):
            state, loss = train_step(state, tokens)
            losses.append(float(loss))
        assert losses[-1] < losses[0]
        assert int(state["opt"]["step"]) == 3

    def test_ring_and_dense_attention_agree(self):
        """The same params produce (numerically) the same loss whether the
        sequence is ring-sharded or not — SP is a pure layout change."""
        mesh = make_mesh()
        base = TransformerConfig(**_TINY)
        model_d = Transformer(base)
        model_r = Transformer(
            TransformerConfig(**_TINY, use_ring_attention=True)
        )
        params = model_d.shard_params(model_d.init(jax.random.PRNGKey(0)), mesh)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 128)
        loss_d = jax.jit(model_d.loss)(params, tokens)
        loss_r = jax.jit(functools.partial(model_r.loss, mesh=mesh))(
            params,
            jax.device_put(tokens, NamedSharding(mesh, P("data", "fsdp"))),
        )
        np.testing.assert_allclose(float(loss_d), float(loss_r), rtol=2e-2)

    def test_param_specs_cover_params(self):
        cfg = TransformerConfig(**_TINY, n_experts=4)
        model = Transformer(cfg)
        params = model.init(jax.random.PRNGKey(0))
        specs = model.param_specs()
        assert jax.tree.structure(
            params
        ) == jax.tree.structure(specs, is_leaf=lambda s: isinstance(s, P))

    def test_sharded_train_state_snapshot_roundtrip(self, tmp_path):
        """Checkpoint the fully-sharded train state (fsdp/tp/ep layouts)
        and restore into a zeroed state under the same mesh."""
        from tpusnap import PytreeState, Snapshot
        from tpusnap.test_utils import check_state_dict_eq

        mesh = make_mesh()
        cfg = TransformerConfig(**_TINY, n_experts=4)
        model = Transformer(cfg)
        state = init_train_state(model, mesh, jax.random.PRNGKey(0))
        Snapshot.take(str(tmp_path / "snap"), {"ts": PytreeState(state)})
        target = PytreeState(jax.tree.map(jnp.zeros_like, state))
        Snapshot(str(tmp_path / "snap")).restore({"ts": target})
        assert check_state_dict_eq(state, target.tree)
        for before, after in zip(
            jax.tree.leaves(state), jax.tree.leaves(target.tree)
        ):
            assert after.sharding == before.sharding

    def test_restore_into_different_mesh_shape(self, tmp_path):
        """Elasticity: save under (2,2,2), restore under (1,4,2) — the
        sharded preparer reshards on load."""
        from tpusnap import PytreeState, Snapshot

        cfg = TransformerConfig(**_TINY)
        model = Transformer(cfg)
        mesh_a = make_mesh(mesh_shape=(2, 2, 2))
        state = init_train_state(model, mesh_a, jax.random.PRNGKey(0))
        Snapshot.take(str(tmp_path / "snap"), {"ts": PytreeState(state)})

        mesh_b = make_mesh(mesh_shape=(1, 4, 2))
        state_b = init_train_state(model, mesh_b, jax.random.PRNGKey(7))
        target = PytreeState(state_b)
        Snapshot(str(tmp_path / "snap")).restore({"ts": target})
        for before, after in zip(
            jax.tree.leaves(state), jax.tree.leaves(target.tree)
        ):
            np.testing.assert_array_equal(np.asarray(before), np.asarray(after))


class TestFlashAttention:
    """Pallas flash kernel (ops/flash_attention.py), interpret mode on CPU."""

    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize(
        "shape", [(2, 16, 2, 8), (1, 200, 4, 64)], ids=["tiny", "padded"]
    )
    def test_matches_dense(self, causal, shape):
        from tpusnap.ops import flash_attention
        from tpusnap.ops.flash_attention import _attention_reference

        b, s, h, d = shape
        q, k, v = (
            jax.random.normal(kk, shape, jnp.float32)
            for kk in jax.random.split(jax.random.PRNGKey(1), 3)
        )
        out = flash_attention(
            q, k, v, causal=causal, block_q=64, block_k=64, interpret=True
        )
        ref = _attention_reference(q, k, v, causal)
        np.testing.assert_allclose(out, ref, atol=2e-5)

    def test_gradients_match_reference(self):
        from tpusnap.ops import flash_attention
        from tpusnap.ops.flash_attention import _attention_reference

        q, k, v = (
            jax.random.normal(kk, (1, 32, 2, 16), jnp.float32)
            for kk in jax.random.split(jax.random.PRNGKey(2), 3)
        )
        g = jax.grad(
            lambda *a: flash_attention(*a, interpret=True).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)
        gr = jax.grad(
            lambda *a: _attention_reference(*a, True).sum(), argnums=(0, 1, 2)
        )(q, k, v)
        for got, want in zip(g, gr):
            np.testing.assert_allclose(got, want, atol=2e-5)

    def test_nothing_selects_the_interpreter_implicitly(self, monkeypatch):
        """Without an explicit interpret request the kernel goes to
        Mosaic — which the CPU backend refuses — and "auto" never maps a
        backend it does not know to some fallback."""
        from tpusnap.ops import flash_attention

        q = jnp.ones((1, 16, 2, 8), jnp.float32)
        with pytest.raises(ValueError, match="interpret mode"):
            flash_attention(q, q, q)
        tokens = jnp.zeros((1, 16), jnp.int32)
        tiny = dict(
            vocab_size=128, d_model=32, n_heads=2, n_layers=1, d_ff=64, max_seq_len=16
        )
        model = Transformer(TransformerConfig(**tiny, attention_impl="flash"))
        params = model.init(jax.random.PRNGKey(0))
        with pytest.raises(ValueError, match="interpret mode"):
            model.apply(params, tokens)
        auto = Transformer(TransformerConfig(**tiny))  # "auto": XLA path on cpu
        assert auto.apply(params, tokens).shape == (1, 16, 128)
        monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
        with pytest.raises(RuntimeError, match="does not know backend 'gpu'"):
            auto.apply(params, tokens)

    def test_model_forward_flash_vs_reference(self):
        tokens = jnp.asarray(
            np.random.default_rng(0).integers(0, 128, (2, 16)), jnp.int32
        )
        logits = {}
        for impl in ("flash_interpret", "reference"):
            cfg = TransformerConfig(
                vocab_size=128,
                d_model=32,
                n_heads=2,
                n_layers=2,
                d_ff=64,
                max_seq_len=16,
                dtype=jnp.float32,
                attention_impl=impl,
            )
            model = Transformer(cfg)
            params = model.init(jax.random.PRNGKey(0))
            logits[impl] = model.apply(params, tokens)
        np.testing.assert_allclose(
            logits["flash_interpret"], logits["reference"], atol=1e-4
        )
