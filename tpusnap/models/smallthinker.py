"""One chip's share of a sparse-expert decoder with window and global
attention (the SmallThinker layer, arXiv:2507.20984) — pure JAX.

What differs from :mod:`.transformer`, and why it is a module of its own:

- **One subtree a layer, nothing stacked.** The layers of a period differ
  in kind (``rope_layout`` / ``window_layout``: a global layer without
  positional encoding, then window layers with RoPE), so the forward is a
  Python loop over ``params["layers"]["00"]``, ``["01"]``, ... and every
  layer's leaves are their own arrays: a checkpoint of this model is many
  leaves of a few tens of MiB, not a few stacked ones.
- **An expert layer that is told which experts it holds.** The router
  keeps all ``n_experts`` outputs and its top ``top_k``; this share holds
  experts ``first_expert .. first_expert + n_held_experts`` as banks
  ``[held, d_model, d_expert]`` and computes their part of the result for
  the tokens routed to them. What the absent experts would add is left
  out (the other shares add it; the expert-parallel exchange is not
  here). Token-expert pairs are sorted by expert and only the routed rows
  are multiplied (``lax.ragged_dot`` over all ``tokens x top_k`` sorted
  rows, of which the grouped product skips those past the last group), so
  no token is dropped at any imbalance. Fetching the pairs' rows and
  putting them back costs the same whatever the routing; only the
  products' time follows it.
- **Grouped-query attention in query blocks.** Each block of
  ``q_block`` queries reads only the keys it can see (all earlier ones,
  or the last ``window``), and is recomputed in the backward, so no
  ``seq x seq`` array of all heads is ever live.

The model keeps :class:`~.transformer.Transformer`'s surface (``init``,
``param_specs``, ``loss(params, tokens, mesh=...)``, ``config``), so
``make_train_step``, ``init_train_state`` and ``train_state_shardings``
serve both. Products take bf16 operands and accumulate in f32; the
parameters, the residual stream, the norms and the router's logits are f32.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from .transformer import _rmsnorm, _rope

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class SmallThinkerConfig:
    vocab_size: int = 18992  # the rows of the vocabulary held here
    d_model: int = 2560
    n_heads: int = 7  # query heads held here
    n_kv_heads: int = 1  # KV heads held here
    head_dim: int = 128
    n_layers: int = 4
    d_expert: int = 768
    n_experts: int = 64  # the router's outputs
    top_k: int = 6
    first_expert: int = 0  # the experts held here: first .. first + n_held
    n_held_experts: int = 8
    window: int = 4096
    rope_theta: float = 1.5e6
    rope_layout: Tuple[int, ...] = (0, 1, 1, 1)  # 1: the layer rotates q and k
    window_layout: Tuple[int, ...] = (0, 1, 1, 1)  # 1: the layer sees `window` keys
    q_block: int = 1024  # queries an attention block holds
    loss_block: int = 1024  # positions whose logits are alive at once
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    use_ring_attention: bool = False  # token_sharding reads it; not offered here

    def __post_init__(self) -> None:
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_heads must be a multiple of n_kv_heads")
        if len(self.rope_layout) < self.n_layers or len(self.window_layout) < self.n_layers:
            raise ValueError("rope_layout and window_layout need an entry a layer")
        if not 0 <= self.first_expert <= self.n_experts - self.n_held_experts:
            raise ValueError("the held experts must lie among the router's outputs")
        if self.use_ring_attention:
            raise ValueError("this model has no ring attention")


def layer_name(index: int) -> str:
    return f"{index:02d}"


class SmallThinker:
    """Functional model: ``init`` → params pytree, ``loss`` → scalar."""

    def __init__(self, config: SmallThinkerConfig) -> None:
        self.config = config

    # ------------------------------------------------------------------ init

    def init(self, key: jax.Array) -> Params:
        cfg = self.config
        D, V, F, E = cfg.d_model, cfg.vocab_size, cfg.d_expert, cfg.n_held_experts
        q_width, kv_width = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
        keys = jax.random.split(key, 2 + cfg.n_layers)

        def norm(k, *shape, fan_in):
            return jax.random.normal(k, shape, cfg.param_dtype) * fan_in ** -0.5

        def layer(k):
            ks = jax.random.split(k, 8)
            return {
                "ln1": jnp.ones((D,), cfg.param_dtype),
                "ln2": jnp.ones((D,), cfg.param_dtype),
                "router": norm(ks[0], D, cfg.n_experts, fan_in=D),
                "wq": norm(ks[1], D, q_width, fan_in=D),
                "wk": norm(ks[2], D, kv_width, fan_in=D),
                "wv": norm(ks[3], D, kv_width, fan_in=D),
                "wo": norm(ks[4], q_width, D, fan_in=q_width),
                "w_gate": norm(ks[5], E, D, F, fan_in=D),
                "w_up": norm(ks[6], E, D, F, fan_in=D),
                "w_down": norm(ks[7], E, F, D, fan_in=F),
            }

        return {
            "embed": norm(keys[0], V, D, fan_in=D),
            "layers": {layer_name(i): layer(keys[2 + i]) for i in range(cfg.n_layers)},
            "ln_f": jnp.ones((D,), cfg.param_dtype),
            "unembed": norm(keys[1], D, V, fan_in=D),
        }

    # ------------------------------------------------------- sharding specs

    def param_specs(self) -> Params:
        """Every leaf replicated over the ("data", "fsdp", "tensor") mesh:
        this model IS one chip's share (its heads, experts and vocabulary
        rows are already the slice a chip holds); a mesh of several chips
        runs it data-parallel."""
        shapes = jax.eval_shape(self.init, jax.random.PRNGKey(0))
        return jax.tree.map(lambda s: P(*([None] * s.ndim)), shapes)

    # --------------------------------------------------------------- forward

    def loss(
        self, params: Params, tokens: jax.Array, mesh: Optional[Mesh] = None
    ) -> jax.Array:
        """Mean next-token cross-entropy over the held vocabulary rows (the
        last position predicts nothing). ``mesh`` is unused: the signature
        is :meth:`Transformer.loss`'s, for ``make_train_step``."""
        cfg = self.config
        # The residual stream stays float32 (products read it in
        # ``cfg.dtype``): rounding it a layer would move the next router's
        # logits, and a top-k choice flips on a near tie.
        x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
        for i in range(cfg.n_layers):
            # Each layer is recomputed in the backward: only its input
            # stays alive across the step.
            x = jax.checkpoint(self._layer, static_argnums=(2,))(
                params["layers"][layer_name(i)], x, i
            )
        x = _rmsnorm(x, params["ln_f"]).astype(cfg.dtype)
        return self._blocked_nll(x, params["unembed"], tokens)

    def _layer(self, lp: Params, x: jax.Array, index: int) -> jax.Array:
        cfg = self.config
        # The router reads the attention block's normed input, in float32;
        # the expert products read the layer's second norm.
        a = _rmsnorm(x, lp["ln1"])
        x = x + self._attention(lp, a.astype(cfg.dtype), index)
        return x + self.experts(lp, a, _rmsnorm(x, lp["ln2"]).astype(cfg.dtype))

    def _attention(self, lp: Params, a: jax.Array, index: int) -> jax.Array:
        cfg = self.config
        b, s, _ = a.shape
        q = jnp.einsum("bsd,dz->bsz", a, lp["wq"].astype(cfg.dtype))
        k = jnp.einsum("bsd,dz->bsz", a, lp["wk"].astype(cfg.dtype))
        v = jnp.einsum("bsd,dz->bsz", a, lp["wv"].astype(cfg.dtype))
        q = q.reshape(b, s, cfg.n_heads, cfg.head_dim)
        k = k.reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
        v = v.reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
        if cfg.rope_layout[index]:
            q, k = _rope(q, cfg.rope_theta), _rope(k, cfg.rope_theta)
        window = cfg.window if cfg.window_layout[index] else None
        with jax.named_scope("attn.window" if window else "attn.global"):
            out = blocked_attention(q, k, v, window=window, q_block=cfg.q_block)
        out = out.reshape(b, s, cfg.n_heads * cfg.head_dim)
        return jnp.einsum(
            "bsz,zd->bsd", out, lp["wo"].astype(cfg.dtype), preferred_element_type=jnp.float32
        )

    def experts(self, lp: Params, a: jax.Array, x: jax.Array) -> jax.Array:
        """This share's part of the expert layer's output: for every token
        ``sum over e in top_k(router(a)) and held here of w_e * down_e(
        relu(gate_e x) * up_e x)``, the weights the softmax over the top
        ``top_k`` router logits."""
        cfg = self.config
        shape = x.shape
        a, x = a.reshape(-1, shape[-1]), x.reshape(-1, shape[-1])
        k, held = cfg.top_k, cfg.n_held_experts
        with jax.named_scope("moe.route"):
            logits = jnp.matmul(
                a.astype(jnp.float32), lp["router"].astype(jnp.float32),
                precision=lax.Precision.HIGHEST,
            )
            top, chosen = lax.top_k(logits, k)
            weights = jax.nn.softmax(top, axis=-1).reshape(-1)
            # A pair's expert as this share numbers it; `held` for a pair
            # whose expert lives elsewhere, so that it sorts last.
            local = chosen.reshape(-1) - cfg.first_expert
            local = jnp.where((local >= 0) & (local < held), local, held)
            order = jnp.argsort(local)  # stable: pairs grouped by expert
            back = jnp.argsort(order)  # where each pair went
            sizes = jnp.sum(local[:, None] == jnp.arange(held), axis=0, dtype=jnp.int32)
        # A row past the last group belongs to no expert held here. A
        # grouped product leaves such rows of its result as it finds them
        # (on a TPU: not zeroed, not even finite), so they are cut off
        # going in and after every product: no operand and no cotangent of
        # a product ever holds one.
        routed = (jnp.arange(order.size) < jnp.sum(sizes))[:, None]

        def product(lhs, bank, **kw):
            return jnp.where(routed, lax.ragged_dot(lhs, lp[bank].astype(cfg.dtype), sizes, **kw), 0)

        with jax.named_scope("moe.experts"):
            # Every pair's row is fetched and put back, whatever the
            # routing (a fixed cost); the products skip what is not routed.
            xs = jnp.where(routed, _pairs_of_tokens(x, order, back, k), 0)
            h = jax.nn.relu(product(xs, "w_gate")) * product(xs, "w_up")
            out = product(h, "w_down", preferred_element_type=jnp.float32)
            y = _tokens_of_pairs(out * weights[order][:, None], order, back, k)
        return y.reshape(shape)

    # ------------------------------------------------------------------ loss

    def _blocked_nll(self, x: jax.Array, unembed: jax.Array, tokens: jax.Array) -> jax.Array:
        """Logits and their log-sum-exp for ``loss_block`` positions at a
        time, each block recomputed in the backward."""
        cfg = self.config
        b, s, d = x.shape
        block = min(cfg.loss_block, s)
        if s % block:
            raise ValueError(f"seq_len {s} is no multiple of loss_block {block}")
        n = s // block
        # Position i is scored against token i + 1; the last one against nothing.
        targets = jnp.concatenate([tokens[:, 1:], jnp.zeros((b, 1), tokens.dtype)], axis=1)
        counted = (jnp.arange(s) < s - 1).astype(jnp.float32)
        w = unembed.astype(cfg.dtype)

        def body(total, blk):
            xb, tb, cb = blk
            logits = jnp.einsum("bsd,dv->bsv", xb, w, preferred_element_type=jnp.float32)
            picked = jnp.take_along_axis(logits, tb[..., None], axis=-1)[..., 0]
            nll = jax.nn.logsumexp(logits, axis=-1) - picked
            return total + jnp.sum(nll * cb), None

        blocks = (
            x.reshape(b, n, block, d).swapaxes(0, 1),
            targets.reshape(b, n, block).swapaxes(0, 1),
            counted.reshape(n, 1, block),
        )
        total, _ = lax.scan(jax.checkpoint(body), jnp.zeros((), jnp.float32), blocks)
        return total / (b * (s - 1))


# Row ``r`` of the sorted order is pair ``order[r]``, the ``order[r] % k``-th
# choice of token ``order[r] // k``; pair ``p`` lies at row ``back[p]``. Both
# ways between tokens and sorted pairs are gathers, and each is the other's
# transpose: left to autodiff, either's backward would be a scatter-add of
# every row, which a TPU does a row at a time.


def _gather_pairs(x, order, k):
    return x[order // k]


def _sum_pairs(rows, back, k):
    # One gather a choice, added up in float32: gathering all pairs at once
    # and reshaping to [tokens, k, d] costs a relayout (k is no tile's size).
    chosen = back.reshape(-1, k)
    total = rows[chosen[:, 0]].astype(jnp.float32)
    for j in range(1, k):
        total = total + rows[chosen[:, j]]
    return total.astype(rows.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _pairs_of_tokens(x, order, back, k):
    """``[tokens, d] -> [pairs, d]``: each pair's token's row, in sorted order."""
    return _gather_pairs(x, order, k)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _tokens_of_pairs(rows, order, back, k):
    """``[pairs, d] -> [tokens, d]``: each token's pairs' rows, added up."""
    return _sum_pairs(rows, back, k)


_pairs_of_tokens.defvjp(
    lambda x, order, back, k: (_gather_pairs(x, order, k), back),
    lambda k, back, g: (_sum_pairs(g, back, k), None, None),
)
_tokens_of_pairs.defvjp(
    lambda rows, order, back, k: (_sum_pairs(rows, back, k), order),
    lambda k, order, g: (_gather_pairs(g, order, k), None, None),
)


def blocked_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *, window: Optional[int], q_block: int
) -> jax.Array:
    """Causal grouped-query softmax attention, ``q_block`` queries at a
    time. ``q`` is ``[batch, seq, heads, head_dim]``, ``k`` and ``v``
    ``[batch, seq, kv_heads, head_dim]``; query head ``h`` reads KV head
    ``h // (heads // kv_heads)``. Query ``i`` sees keys ``j <= i`` and,
    with a ``window``, ``j > i - window``. A block is handed only the keys
    one of its queries can see (a static slice), and its scores are
    recomputed in the backward."""
    b, s, heads, dh = q.shape
    kv_heads = k.shape[2]
    q = q.reshape(b, s, kv_heads, heads // kv_heads, dh)

    def one(q0, k0, qb, kb, vb):
        scores = jnp.einsum(
            "bqkgd,bskd->bkgqs", qb, kb, preferred_element_type=jnp.float32
        ) * dh ** -0.5
        qi = q0 + jnp.arange(qb.shape[1])[:, None]
        kj = k0 + jnp.arange(kb.shape[1])[None, :]
        seen = kj <= qi
        if window is not None:
            seen &= kj > qi - window
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bkgqs,bskd->bqkgd", probs.astype(vb.dtype), vb)

    out = []
    for q0 in range(0, s, q_block):
        k0 = 0 if window is None else max(0, q0 - window + 1)
        k1 = min(q0 + q_block, s)
        one_block = jax.checkpoint(functools.partial(one, q0, k0))
        out.append(one_block(q[:, q0:k1], k[:, k0:k1], v[:, k0:k1]))
    return jnp.concatenate(out, axis=1).reshape(b, s, heads, dh)
