"""Flagship decoder transformer — pure JAX, mesh-sharded, scan-over-layers.

TPU-first design notes:
- **Stacked layers + ``lax.scan``**: every layer's params carry a leading
  ``n_layers`` dim and the forward scans over them — one layer compiles
  once, XLA pipelines the scan, and the stacked layout is the natural
  unit for pipeline-parallel stage splitting.
- **Sharding by ``PartitionSpec``**: ``param_specs()`` maps the parameter
  pytree to specs over a ``("data", "fsdp", "tensor")`` mesh. Matmul
  weights alternate ``("fsdp", "tensor")`` / ``("tensor", "fsdp")`` so
  TP collectives ride ICI and FSDP all-gathers amortize over layers.
  MoE expert weights shard their expert dim over ``"data"`` (expert
  parallelism). With ``use_ring_attention`` the *sequence* is sharded
  over ``"fsdp"`` (context parallelism): attention runs inside a
  ``jax.shard_map`` with every mesh axis manual — batch→data, seq→fsdp,
  heads→tensor — K/V blocks rotating over the fsdp ring
  (ops/ring_attention.py) while the rest of the model stays under XLA
  auto-sharding on the global view. One model therefore exhibits
  dp / fsdp / tp / sp / ep — every sharding family the checkpoint
  preparers (io_preparers/sharded.py) must round-trip and reshard.
- **bf16 compute, f32 params/optimizer**: matmuls hit the MXU in
  bfloat16; Adam moments and softmax statistics stay f32.

This model exists to *exercise the checkpointing framework* end-to-end
(the reference ships training scripts, not models — SURVEY.md §2
#23/#24); it is still a real, trainable transformer.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.flash_attention import flash_attention
from ..ops.ring_attention import ring_attention

Params = Dict[str, Any]

_ATTENTION_IMPLS = ("auto", "flash", "flash_interpret", "reference")
# What "auto" means per backend. This table is the only place in the
# model that decides from the backend; a backend it does not list is an
# error, never a quiet fallback.
_AUTO_ATTENTION_IMPL = {"tpu": "flash", "cpu": "reference"}


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32768
    d_model: int = 512
    n_heads: int = 8
    n_layers: int = 4
    d_ff: int = 2048
    max_seq_len: int = 1024
    n_experts: int = 0  # 0 → dense FFN; >0 → MoE FFN (EP-sharded weights)
    dtype: Any = jnp.bfloat16  # compute dtype (MXU-friendly)
    param_dtype: Any = jnp.float32
    use_ring_attention: bool = False  # shard the sequence over "fsdp" (CP)
    # Non-ring attention implementation: "auto" → compiled Pallas flash
    # kernel on the "tpu" backend, plain-XLA online softmax on "cpu", an
    # error on any other backend; "flash" is the compiled kernel (fails
    # to lower off-TPU); "flash_interpret" runs the kernel in the Pallas
    # interpreter (CPU tests); "reference" forces the XLA path.
    attention_impl: str = "auto"
    rope_theta: float = 10000.0

    def __post_init__(self) -> None:
        if self.attention_impl not in _ATTENTION_IMPLS:
            raise ValueError(f"unknown attention_impl: {self.attention_impl!r}")

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads


class Transformer:
    """Functional model: ``init`` → params pytree, ``apply`` → logits."""

    def __init__(self, config: TransformerConfig) -> None:
        if config.d_model % config.n_heads:
            raise ValueError("d_model must be divisible by n_heads")
        self.config = config

    # ------------------------------------------------------------------ init

    def init(self, key: jax.Array) -> Params:
        cfg = self.config
        L, D, F, V = cfg.n_layers, cfg.d_model, cfg.d_ff, cfg.vocab_size
        keys = jax.random.split(key, 8)

        def norm(k, *shape, fan_in):
            return jax.random.normal(k, shape, cfg.param_dtype) * fan_in ** -0.5

        params: Params = {
            "embed": norm(keys[0], V, D, fan_in=D),
            "layers": {
                "ln1": jnp.ones((L, D), cfg.param_dtype),
                "ln2": jnp.ones((L, D), cfg.param_dtype),
                "wqkv": norm(keys[1], L, D, 3 * D, fan_in=D),
                "wo": norm(keys[2], L, D, D, fan_in=D),
            },
            "ln_f": jnp.ones((D,), cfg.param_dtype),
            "unembed": norm(keys[3], D, V, fan_in=D),
        }
        if cfg.n_experts:
            E = cfg.n_experts
            params["layers"]["router"] = norm(keys[4], L, D, E, fan_in=D)
            params["layers"]["w1e"] = norm(keys[5], L, E, D, F, fan_in=D)
            params["layers"]["w2e"] = norm(keys[6], L, E, F, D, fan_in=F)
        else:
            params["layers"]["w1"] = norm(keys[5], L, D, F, fan_in=D)
            params["layers"]["w2"] = norm(keys[6], L, F, D, fan_in=F)
        return params

    # ------------------------------------------------------- sharding specs

    def param_specs(self) -> Params:
        """PartitionSpecs over a ("data", "fsdp", "tensor") mesh."""
        cfg = self.config
        specs: Params = {
            "embed": P("fsdp", "tensor"),
            "layers": {
                "ln1": P(None, None),
                "ln2": P(None, None),
                "wqkv": P(None, "fsdp", "tensor"),
                "wo": P(None, "tensor", "fsdp"),
            },
            "ln_f": P(None),
            "unembed": P("tensor", "fsdp"),
        }
        if cfg.n_experts:
            specs["layers"]["router"] = P(None, "fsdp", None)
            # Expert dim over "data" → expert parallelism.
            specs["layers"]["w1e"] = P(None, "data", "fsdp", "tensor")
            specs["layers"]["w2e"] = P(None, "data", "tensor", "fsdp")
        else:
            specs["layers"]["w1"] = P(None, "fsdp", "tensor")
            specs["layers"]["w2"] = P(None, "tensor", "fsdp")
        return specs

    def shard_params(self, params: Params, mesh: Mesh) -> Params:
        specs = self.param_specs()
        return jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, specs
        )

    # --------------------------------------------------------------- forward

    def apply(
        self,
        params: Params,
        tokens: jax.Array,
        mesh: Optional[Mesh] = None,
    ) -> jax.Array:
        """Forward pass → logits [batch, seq, vocab] (f32).

        ``mesh`` is required when ``config.use_ring_attention`` — the
        sequence-parallel attention region is a ``shard_map`` over it.
        Everything outside that region operates on the global logical
        view (RoPE positions, scan over layers, losses) and is sharded
        automatically by XLA.
        """
        cfg = self.config
        if cfg.use_ring_attention and mesh is None:
            raise ValueError("use_ring_attention requires a mesh")
        x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)

        def layer(x, lp):
            x = x + self._attention(lp, _rmsnorm(x, lp["ln1"]), mesh)
            x = x + self._ffn(lp, _rmsnorm(x, lp["ln2"]))
            return x, None

        x, _ = lax.scan(layer, x, params["layers"])
        x = _rmsnorm(x, params["ln_f"])
        return jnp.einsum(
            "bsd,dv->bsv", x, params["unembed"].astype(cfg.dtype),
            preferred_element_type=jnp.float32,
        )

    def _attention(self, lp, x, mesh):
        cfg = self.config
        b, s, _ = x.shape
        qkv = jnp.einsum("bsd,dz->bsz", x, lp["wqkv"].astype(cfg.dtype))
        q, k, v = jnp.split(qkv, 3, axis=-1)
        shape = (b, s, cfg.n_heads, cfg.head_dim)
        q, k, v = (t.reshape(shape) for t in (q, k, v))
        # RoPE on the global view: positions are plain global indices.
        q = _rope(q, cfg.rope_theta)
        k = _rope(k, cfg.rope_theta)
        if cfg.use_ring_attention:
            # Fully-manual region: batch→data, sequence→fsdp, heads→tensor.
            # Heads are independent (no collective on "tensor"); K/V blocks
            # rotate over the "fsdp" ring.
            spec = P("data", "fsdp", "tensor", None)
            out = jax.shard_map(
                functools.partial(ring_attention, axis_name="fsdp", causal=True),
                mesh=mesh,
                in_specs=(spec, spec, spec),
                out_specs=spec,
            )(q, k, v)
        else:
            impl = cfg.attention_impl
            if impl == "auto":
                backend = jax.default_backend()
                if backend not in _AUTO_ATTENTION_IMPL:
                    raise RuntimeError(
                        f'attention_impl="auto" does not know backend '
                        f"{backend!r}; choose one of {_ATTENTION_IMPLS[1:]}"
                    )
                impl = _AUTO_ATTENTION_IMPL[backend]
            if impl == "reference":
                out = ring_attention(q, k, v, axis_name=None, causal=True)
            else:
                out = flash_attention(
                    q, k, v, causal=True, interpret=impl == "flash_interpret"
                )
        out = out.reshape(b, s, cfg.d_model)
        return jnp.einsum("bsd,dz->bsz", out, lp["wo"].astype(cfg.dtype))

    def _ffn(self, lp, x):
        cfg = self.config
        if not cfg.n_experts:
            h = jnp.einsum("bsd,df->bsf", x, lp["w1"].astype(cfg.dtype))
            h = jax.nn.gelu(h)
            return jnp.einsum("bsf,fd->bsd", h, lp["w2"].astype(cfg.dtype))
        # MoE with dense soft routing (every token weighted over all
        # experts). The *weights* are EP-sharded; XLA inserts the gathers.
        # This branch exists for the expert-sharded layout alone. Top-k
        # token dispatch over the experts a chip holds (sorted pairs,
        # grouped products, no token dropped) is models/smallthinker.py;
        # its all-to-all between shares is still not here.
        gates = jax.nn.softmax(
            jnp.einsum("bsd,de->bse", x, lp["router"].astype(cfg.dtype)), axis=-1
        )
        h = jnp.einsum("bsd,edf->bsef", x, lp["w1e"].astype(cfg.dtype))
        h = jax.nn.gelu(h)
        out = jnp.einsum("bsef,efd->bsed", h, lp["w2e"].astype(cfg.dtype))
        return jnp.einsum("bsed,bse->bsd", out, gates)

    # ------------------------------------------------------------------ loss

    def loss(
        self, params: Params, tokens: jax.Array, mesh: Optional[Mesh] = None
    ) -> jax.Array:
        """Next-token cross-entropy (last position predicts nothing)."""
        logits = self.apply(params, tokens, mesh=mesh)
        logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
        tgt = tokens[:, 1:]
        nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
        return nll.mean()


def _rmsnorm(x, scale, eps=1e-6):
    xf = x.astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * rms).astype(x.dtype) * scale.astype(x.dtype)


def _rope(x, theta):
    """Rotary position embedding over global positions."""
    b, s, h, d = x.shape
    pos = jnp.arange(s)
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None].astype(jnp.float32) * freqs[None, :]  # [s, d/2]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., 0::2].astype(jnp.float32), x[..., 1::2].astype(jnp.float32)
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(b, s, h, d).astype(x.dtype)


# ------------------------------------------------------------------ training


def make_mesh(
    devices=None, mesh_shape: Optional[Tuple[int, int, int]] = None
) -> Mesh:
    """Build a ("data", "fsdp", "tensor") mesh over the given devices."""
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    if mesh_shape is None:
        mesh_shape = _default_mesh_shape(n)
    if int(np.prod(mesh_shape)) != n:
        raise ValueError(f"mesh_shape {mesh_shape} != {n} devices")
    arr = np.asarray(devices).reshape(mesh_shape)
    return Mesh(arr, ("data", "fsdp", "tensor"))


def _default_mesh_shape(n: int) -> Tuple[int, int, int]:
    """Split n devices into (data, fsdp, tensor), preferring fsdp×tensor
    inner axes (ICI-adjacent) of 2×2 when divisible."""
    if n % 4 == 0:
        return (n // 4, 2, 2)
    if n % 2 == 0:
        return (n // 2, 1, 2)
    return (n, 1, 1)


def make_train_step(model: Transformer, mesh: Mesh, learning_rate: float = 1e-3):
    """Jitted SPMD train step ``(state, tokens) -> (state, loss)``, for
    any model with ``loss(params, tokens, mesh=...)``, ``param_specs()``
    and ``config`` (:class:`Transformer`, :class:`~.smallthinker.SmallThinker`).

    ``state = {"params": ..., "opt": {"mu": ..., "nu": ..., "step": ...}}``
    (Adam; f32 moments sharded like their params). Tokens are sharded
    by :func:`token_sharding`.
    """
    state_shardings = train_state_shardings(model, mesh)
    b1, b2, eps = 0.9, 0.999, 1e-8

    def train_step(state, tokens):
        loss, grads = jax.value_and_grad(model.loss)(
            state["params"], tokens, mesh=mesh
        )
        step = state["opt"]["step"] + 1
        bc1 = 1.0 - b1 ** step.astype(jnp.float32)
        bc2 = 1.0 - b2 ** step.astype(jnp.float32)

        def upd(p, g, mu, nu):
            g = g.astype(jnp.float32)
            mu = b1 * mu + (1 - b1) * g
            nu = b2 * nu + (1 - b2) * g * g
            p_new = p.astype(jnp.float32) - learning_rate * (mu / bc1) / (
                jnp.sqrt(nu / bc2) + eps
            )
            return p_new.astype(p.dtype), mu, nu

        out = jax.tree.map(
            upd, state["params"], grads, state["opt"]["mu"], state["opt"]["nu"]
        )
        is_triple = lambda t: isinstance(t, tuple)  # noqa: E731
        params = jax.tree.map(lambda t: t[0], out, is_leaf=is_triple)
        mu = jax.tree.map(lambda t: t[1], out, is_leaf=is_triple)
        nu = jax.tree.map(lambda t: t[2], out, is_leaf=is_triple)
        new_state = {"params": params, "opt": {"mu": mu, "nu": nu, "step": step}}
        return new_state, loss

    return jax.jit(
        train_step,
        in_shardings=(state_shardings, token_sharding(model.config, mesh)),
        out_shardings=(state_shardings, NamedSharding(mesh, P())),
    )


def token_sharding(cfg: TransformerConfig, mesh: Mesh) -> NamedSharding:
    """Sharding of a ``[batch, seq]`` token batch: ``P("data", "fsdp")``
    under ring attention — the sequence rides the "fsdp" axis as context
    parallelism — else ``P(("data", "fsdp"), None)`` (batch over both)."""
    spec = (
        P("data", "fsdp") if cfg.use_ring_attention else P(("data", "fsdp"), None)
    )
    return NamedSharding(mesh, spec)


def random_tokens(
    cfg: TransformerConfig,
    mesh: Mesh,
    rng: np.random.Generator,
    batch: int,
    seq_len: Optional[int] = None,
) -> jax.Array:
    """A ``[batch, seq_len]`` int32 batch drawn from ``rng``, placed with
    :func:`token_sharding`. ``seq_len`` defaults to ``cfg.max_seq_len``."""
    shape = (batch, seq_len if seq_len is not None else cfg.max_seq_len)
    tokens = rng.integers(0, cfg.vocab_size, shape).astype(np.int32)
    return jax.device_put(tokens, token_sharding(cfg, mesh))


def train_state_specs(model: Transformer) -> Params:
    specs = model.param_specs()
    return {"params": specs, "opt": {"mu": specs, "nu": specs, "step": P()}}


def train_state_shardings(model: Transformer, mesh: Mesh) -> Params:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        train_state_specs(model),
        is_leaf=lambda s: isinstance(s, P),
    )


def init_train_state(model: Transformer, mesh: Mesh, key: jax.Array) -> Params:
    """Sharded params + zero-initialized Adam state.

    Built under ``jit`` with ``out_shardings``, so every device
    materializes only its own shards: building the arrays unsharded
    first would put the whole state on the default device before the
    ``device_put``."""

    def init(key):
        params = model.init(key)
        zeros = lambda p: jnp.zeros(p.shape, jnp.float32)  # noqa: E731
        return {
            "params": params,
            "opt": {
                "mu": jax.tree.map(zeros, params),
                "nu": jax.tree.map(zeros, params),
                "step": jnp.zeros((), jnp.int32),
            },
        }

    return jax.jit(init, out_shardings=train_state_shardings(model, mesh))(key)
