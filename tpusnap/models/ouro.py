"""A looped decoder: one stack of layers run several times over the same
weights, with an exit gate and a loss at every pass (the Ouro LoopLM
layer, "Scaling Latent Reasoning via Looped Language Models", 2025) —
pure JAX.

What differs from :mod:`.transformer`, and why it is a module of its own:

- **The stack runs ``n_passes`` times over one set of leaves.** A scan
  over passes around the scan over stacked layers: pass ``t`` reads what
  pass ``t - 1`` gave, through the *same* ``params["layers"]``, so a
  leaf's gradient is the sum over its ``n_passes`` uses. Every layer
  application is recomputed in the backward (``jax.checkpoint``): only
  its input stays alive, ``n_passes * n_layers`` of them a step.
- **Sandwich norms.** Four RMSNorm scales a layer: one before and one
  after each sublayer, ``a = h + ln2(attn(ln1(h)))``,
  ``h' = a + ln4(mlp(ln3(a)))``; the MLP is SwiGLU; no matrix has a bias.
  The final norm closes *every* pass, and its output is what the next
  pass, that pass's head and that pass's gate read.
- **A loss with a term a pass.** After pass ``t`` the head gives that
  pass's next-token cross-entropy ``CE(t)`` and one gate (the same in
  every pass) gives ``lambda(t) = sigmoid(x(t) . w + b)``. The exit
  distribution is ``p(t) = lambda(t) * prod_{j<t} (1 - lambda(j))`` for
  ``t < n_passes`` and the rest for the last pass; the loss is the mean
  over the positions that have a next token of ``sum_t p(t) CE(t) -
  entropy_weight * H(p)``. So the head and the cross-entropy run
  ``n_passes`` times a step, in blocks of positions, each block
  recomputed in the backward.

The model keeps :class:`~.transformer.Transformer`'s surface (``init``,
``param_specs``, ``shard_params``, ``loss(params, tokens, mesh=...)``,
``config``), so ``make_train_step``, ``init_train_state`` and
``train_state_shardings`` serve it as they are. Products take bf16
operands and accumulate in f32; the parameters, the residual stream, the
norms, the softmax, the gate, the exit distribution and the loss are f32.

Named scopes, for the traces: ``loop.pass`` around the stack of one pass
and the norm that closes it (forward, recompute and backward of every
layer application),
``attn.global`` inside it around scores, mask, softmax and value product,
``exit.head`` around a pass's head and cross-entropy, ``exit.gate``
around the gate, the exit distribution and the entropy term.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .smallthinker import blocked_attention
from .transformer import _rmsnorm

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class OuroConfig:
    vocab_size: int = 49152
    d_model: int = 2048
    n_heads: int = 16  # no grouping: as many KV heads
    head_dim: int = 128
    n_layers: int = 4  # the layers held; every pass runs all of them
    d_ff: int = 5632
    n_passes: int = 4  # how often the stack runs (the source's total_ut_steps)
    rope_theta: float = 1e6
    entropy_weight: float = 0.1  # beta: the weight of the exit distribution's entropy
    q_block: int = 1024  # queries an attention block holds
    loss_block: int = 1024  # positions whose logits are alive at once
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    use_ring_attention: bool = False  # token_sharding reads it; not offered here

    def __post_init__(self) -> None:
        if self.n_passes < 1 or self.n_layers < 1:
            raise ValueError("a looped model needs at least one layer and one pass")
        if self.head_dim % 2:
            raise ValueError("RoPE pairs the two halves of a head: head_dim must be even")
        if self.use_ring_attention:
            raise ValueError("this model has no ring attention")


class Ouro:
    """Functional model: ``init`` → params pytree, ``loss`` → scalar."""

    def __init__(self, config: OuroConfig) -> None:
        self.config = config

    # ------------------------------------------------------------------ init

    def init(self, key: jax.Array) -> Params:
        cfg = self.config
        L, D, F, V = cfg.n_layers, cfg.d_model, cfg.d_ff, cfg.vocab_size
        width = cfg.n_heads * cfg.head_dim
        keys = jax.random.split(key, 10)

        def norm(k, *shape, fan_in):
            return jax.random.normal(k, shape, cfg.param_dtype) * fan_in ** -0.5

        def ones(*shape):
            return jnp.ones(shape, cfg.param_dtype)

        return {
            "embed": norm(keys[0], V, D, fan_in=D),
            "layers": {
                "ln1": ones(L, D), "ln2": ones(L, D), "ln3": ones(L, D), "ln4": ones(L, D),
                "wq": norm(keys[2], L, D, width, fan_in=D),
                "wk": norm(keys[3], L, D, width, fan_in=D),
                "wv": norm(keys[4], L, D, width, fan_in=D),
                "wo": norm(keys[5], L, width, D, fan_in=width),
                "w_gate": norm(keys[6], L, D, F, fan_in=D),
                "w_up": norm(keys[7], L, D, F, fan_in=D),
                "w_down": norm(keys[8], L, F, D, fan_in=F),
            },
            "ln_f": ones(D),
            # The head. Named so that it stands before ``embed`` in the
            # tree's order: the two are the state's largest leaves, and of a
            # take's largest leaves the codec policy samples the first. An
            # embedding's Adam moments are zero but for the rows of tokens
            # seen so far; a job's first saves would let them speak for the
            # whole state, and the policy's choice would turn on noise.
            "decode": norm(keys[1], D, V, fan_in=D),
            # One gate for all passes: a vector and a bias of one element.
            "gate": {"w": norm(keys[9], D, fan_in=D), "b": jnp.zeros((1,), cfg.param_dtype)},
        }

    # ------------------------------------------------------- sharding specs

    def param_specs(self) -> Params:
        """Every leaf replicated over the ("data", "fsdp", "tensor") mesh: a
        mesh of several chips runs this model data-parallel."""
        shapes = jax.eval_shape(self.init, jax.random.PRNGKey(0))
        return jax.tree.map(lambda s: P(*([None] * s.ndim)), shapes)

    def shard_params(self, params: Params, mesh: Mesh) -> Params:
        return jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, self.param_specs()
        )

    # --------------------------------------------------------------- forward

    def loss(
        self, params: Params, tokens: jax.Array, mesh: Optional[Mesh] = None
    ) -> jax.Array:
        """The mean over the positions that have a next token of ``sum_t
        p(t) CE(t) - entropy_weight * H(p)`` (see the module's docstring).
        ``mesh`` is unused: the signature is :meth:`Transformer.loss`'s, for
        ``make_train_step``."""
        cfg = self.config
        b, s = tokens.shape
        # The residual stream stays float32 (products read it in
        # ``cfg.dtype``): what one pass rounds, the next three read again.
        x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
        layer = jax.checkpoint(self._layer)  # only a layer's input outlives its application

        def one_pass(carry, t):
            x, log_stay, total = carry
            with jax.named_scope("loop.pass"):
                h, _ = lax.scan(lambda h, lp: (layer(lp, h), None), x, params["layers"])
                x = _rmsnorm(h, params["ln_f"])
            with jax.named_scope("exit.head"):
                ce = self._blocked_nll(x.astype(cfg.dtype), params["decode"], tokens)
            with jax.named_scope("exit.gate"):
                z = jnp.einsum("bsd,d->bs", x, params["gate"]["w"].astype(jnp.float32),
                               precision=lax.Precision.HIGHEST)
                z = z + params["gate"]["b"].astype(jnp.float32)
                # log p(t) = log lambda(t) + sum_{j<t} log(1 - lambda(j)); the
                # last pass takes what is left. In logarithms, so that p log p
                # is finite wherever a gate saturates.
                log_p = jnp.where(t == cfg.n_passes - 1, log_stay,
                                  jax.nn.log_sigmoid(z) + log_stay)
                p = jnp.exp(log_p)
                total = total + p * (ce + cfg.entropy_weight * log_p)
                log_stay = log_stay + jax.nn.log_sigmoid(-z)
            return (x, log_stay, total), None

        zeros = jnp.zeros((b, s), jnp.float32)
        (_, _, total), _ = lax.scan(one_pass, (x, zeros, zeros), jnp.arange(cfg.n_passes))
        # The last position predicts nothing: no term of it counts.
        return jnp.sum(total[:, :-1]) / (b * (s - 1))

    def _layer(self, lp: Params, h: jax.Array) -> jax.Array:
        cfg = self.config
        # Named here as well as around the scan over layers: what a
        # checkpoint inside this one (an attention block) recomputes is
        # named from this function's body, not from its caller's.
        with jax.named_scope("loop.pass"):
            a = h + _rmsnorm(
                self._attention(lp, _rmsnorm(h, lp["ln1"]).astype(cfg.dtype)), lp["ln2"])
            return a + _rmsnorm(self._mlp(lp, _rmsnorm(a, lp["ln3"]).astype(cfg.dtype)), lp["ln4"])

    def _attention(self, lp: Params, u: jax.Array) -> jax.Array:
        cfg = self.config
        b, s, _ = u.shape
        shape = (b, s, cfg.n_heads, cfg.head_dim)
        q = jnp.einsum("bsd,dz->bsz", u, lp["wq"].astype(cfg.dtype)).reshape(shape)
        k = jnp.einsum("bsd,dz->bsz", u, lp["wk"].astype(cfg.dtype)).reshape(shape)
        v = jnp.einsum("bsd,dz->bsz", u, lp["wv"].astype(cfg.dtype)).reshape(shape)
        q, k = _rope_halves(q, cfg.rope_theta), _rope_halves(k, cfg.rope_theta)
        with jax.named_scope("attn.global"):
            out = blocked_attention(q, k, v, window=None, q_block=cfg.q_block)
        out = out.reshape(b, s, cfg.n_heads * cfg.head_dim)
        return jnp.einsum(
            "bsz,zd->bsd", out, lp["wo"].astype(cfg.dtype), preferred_element_type=jnp.float32
        )

    def _mlp(self, lp: Params, u: jax.Array) -> jax.Array:
        cfg = self.config
        gate = jnp.einsum("bsd,df->bsf", u, lp["w_gate"].astype(cfg.dtype))
        up = jnp.einsum("bsd,df->bsf", u, lp["w_up"].astype(cfg.dtype))
        return jnp.einsum(
            "bsf,fd->bsd", jax.nn.silu(gate) * up, lp["w_down"].astype(cfg.dtype),
            preferred_element_type=jnp.float32,
        )

    # ------------------------------------------------------------------ loss

    def _blocked_nll(self, x: jax.Array, decode: jax.Array, tokens: jax.Array) -> jax.Array:
        """``[batch, seq]``: each position's next-token cross-entropy (the
        last position's against token 0: the caller leaves it out). Logits
        and their log-sum-exp for ``loss_block`` positions at a time, each
        block recomputed in the backward."""
        cfg = self.config
        b, s, d = x.shape
        block = min(cfg.loss_block, s)
        if s % block:
            raise ValueError(f"seq_len {s} is no multiple of loss_block {block}")
        n = s // block
        targets = jnp.concatenate([tokens[:, 1:], jnp.zeros((b, 1), tokens.dtype)], axis=1)
        w = decode.astype(cfg.dtype)

        def body(_, blk):
            xb, tb = blk
            logits = jnp.einsum("bsd,dv->bsv", xb, w, preferred_element_type=jnp.float32)
            picked = jnp.take_along_axis(logits, tb[..., None], axis=-1)[..., 0]
            return None, jax.nn.logsumexp(logits, axis=-1) - picked

        blocks = (x.reshape(b, n, block, d).swapaxes(0, 1),
                  targets.reshape(b, n, block).swapaxes(0, 1))
        _, nll = lax.scan(jax.checkpoint(body), None, blocks)
        return nll.swapaxes(0, 1).reshape(b, s)


def _rope_halves(x, theta):
    """Rotary position embedding over the whole head on rotate-half pairs:
    channel ``i`` turns with channel ``i + head_dim / 2`` (the layout of the
    source's code; :func:`.transformer._rope` pairs neighbours). Positions
    are ``0 .. seq - 1``, the same in every pass."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)
