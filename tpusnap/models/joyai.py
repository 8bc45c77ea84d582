"""One chip's share of a latent-attention, sparse-expert decoder with a
multi-token-prediction module (the DeepSeek-V3 layer as JoyAI-LLM-Flash
runs it at d 2048) — pure JAX.

What differs from :mod:`.smallthinker`, and why it is a module of its own:

- **Attention whose keys and values are rebuilt from a low-rank latent
  (MLA).** ``cq = RMSNorm(x W_qa)``, ``q = cq W_qb``, per head ``[q_nope;
  q_rope]``; ``[ckv; k_rope] = x W_kva``, ``c = RMSNorm(ckv)``; ``c W_kvb``
  gives per head ``[k_nope; v]``. RoPE (interleaved pairs) turns ``q_rope``
  and the one ``k_rope`` that all heads share; a head's key is ``[k_nope;
  k_rope]`` and its value is narrower than its key. What a query block is
  handed is the latent ``c`` and ``k_rope`` of the keys it can see: keys and
  values are rebuilt from them in the block, forward and recompute alike,
  so nothing wider than the latent outlives a block. No absorbed form, no
  cache.
- **A layer list whose first layers are dense.** One subtree a layer, as
  :mod:`.smallthinker` has it; the first ``n_dense_layers`` hold a SwiGLU
  of width ``d_ff``, the others an expert layer.
- **A sigmoid router with a correction bias, and a shared expert.**
  ``s = sigmoid(u W_r)`` over all ``n_experts``; the ``top_k`` chosen are
  the largest of ``s + b`` (``b`` a leaf that takes part in the choice
  alone: its gradient is zero); their weights are the chosen ``s`` over
  their sum, times ``routed_scale``. This share holds experts
  ``first_expert .. first_expert + n_held_experts`` and adds their part
  only, through :mod:`.smallthinker`'s sorted pairs and ``lax.ragged_dot``
  (no token dropped at any imbalance); the shared expert, a SwiGLU of the
  experts' width, is added to every token unweighted.
- **A multi-token-prediction module.** With ``h`` the main stack's output
  after the final norm and ``e`` the embedding (the main model's leaf, read
  a second time) of the next token, ``z = [RMSNorm(e); RMSNorm(h)] W_eh``,
  one more expert layer, a norm of its own, then the main model's head (read
  a second time) against the token after the next. The loss is ``CE_main +
  mtp_weight * CE_mtp``. The module runs at the full sequence length: the
  last position's next token is a stand-in that, under the causal mask,
  no counted position sees.

The model keeps :class:`~.transformer.Transformer`'s surface (``init``,
``param_specs``, ``loss(params, tokens, mesh=...)``, ``config``), so
``make_train_step``, ``init_train_state`` and ``train_state_shardings``
serve it as they are. Products take bf16 operands and accumulate in f32;
the parameters, the residual stream, the norms and the router are f32.
Every layer and every attention query block is recomputed in the backward;
both cross-entropies run ``loss_block`` positions at a time.

Named scopes, for the traces: ``latent.proj`` (the four low-rank products
and the two norms between them), ``attn.global`` (scores, mask, softmax,
value product), ``moe.route`` / ``moe.experts`` (as :mod:`.smallthinker`),
``shared.expert``, and ``mtp.combine`` / ``mtp.block`` / ``mtp.head``; the
module's block carries its layer's scopes inside ``mtp.block``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from .smallthinker import _pairs_of_tokens, _tokens_of_pairs, layer_name
from .transformer import _rmsnorm, _rope

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class JoyAIConfig:
    vocab_size: int = 16160  # the rows of the vocabulary held here
    d_model: int = 2048
    n_heads: int = 4  # heads held here
    q_rank: int = 1536  # q_lora_rank
    kv_rank: int = 512  # kv_lora_rank
    d_nope: int = 128  # qk_nope_head_dim
    d_rope: int = 64  # qk_rope_head_dim
    d_v: int = 128  # v_head_dim
    n_layers: int = 5  # the main stack, dense layers included
    n_dense_layers: int = 1  # first_k_dense_replace
    d_ff: int = 7168  # the dense layers' SwiGLU
    d_expert: int = 768  # a routed expert's and the shared expert's SwiGLU
    n_experts: int = 256  # the router's outputs
    top_k: int = 8
    first_expert: int = 0  # the experts held here: first .. first + n_held
    n_held_experts: int = 8
    routed_scale: float = 2.5  # routed_scaling_factor
    n_mtp: int = 1  # num_nextn_predict_layers: 0 or 1
    mtp_weight: float = 0.3  # lambda: the weight of the module's cross-entropy
    rope_theta: float = 3.2e7
    q_block: int = 1024  # queries an attention block holds
    loss_block: int = 1024  # positions whose logits are alive at once
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    use_ring_attention: bool = False  # token_sharding reads it; not offered here

    def __post_init__(self) -> None:
        if not 0 <= self.n_dense_layers <= self.n_layers:
            raise ValueError("the dense layers are the first of the layers held")
        if not 0 <= self.first_expert <= self.n_experts - self.n_held_experts:
            raise ValueError("the held experts must lie among the router's outputs")
        if self.n_mtp not in (0, 1):
            raise ValueError("one multi-token-prediction module, or none")
        if self.d_rope % 2:
            raise ValueError("RoPE turns pairs of channels: d_rope must be even")
        if self.use_ring_attention:
            raise ValueError("this model has no ring attention")


class JoyAI:
    """Functional model: ``init`` → params pytree, ``loss`` → scalar."""

    def __init__(self, config: JoyAIConfig) -> None:
        self.config = config

    # ------------------------------------------------------------------ init

    def init(self, key: jax.Array) -> Params:
        cfg = self.config
        D, V, F, E = cfg.d_model, cfg.vocab_size, cfg.d_expert, cfg.n_held_experts
        H, width = cfg.n_heads, cfg.n_heads * cfg.d_v
        keys = jax.random.split(key, 3 + cfg.n_layers)

        def norm(k, *shape, fan_in):
            return jax.random.normal(k, shape, cfg.param_dtype) * fan_in ** -0.5

        def ones(*shape):
            return jnp.ones(shape, cfg.param_dtype)

        def layer(k, dense):
            ks = jax.random.split(k, 13)
            attention = {
                "ln1": ones(D),
                "ln2": ones(D),
                "ln_kv": ones(cfg.kv_rank),
                "ln_q": ones(cfg.q_rank),
                "wq_a": norm(ks[0], D, cfg.q_rank, fan_in=D),
                "wq_b": norm(ks[1], cfg.q_rank, H * (cfg.d_nope + cfg.d_rope), fan_in=cfg.q_rank),
                "wkv_a": norm(ks[2], D, cfg.kv_rank + cfg.d_rope, fan_in=D),
                "wkv_b": norm(ks[3], cfg.kv_rank, H * (cfg.d_nope + cfg.d_v), fan_in=cfg.kv_rank),
                "wo": norm(ks[4], width, D, fan_in=width),
            }
            if dense:
                return {
                    **attention,
                    "w_gate": norm(ks[5], D, cfg.d_ff, fan_in=D),
                    "w_up": norm(ks[6], D, cfg.d_ff, fan_in=D),
                    "w_down": norm(ks[7], cfg.d_ff, D, fan_in=cfg.d_ff),
                }
            return {
                **attention,
                "router": norm(ks[5], D, cfg.n_experts, fan_in=D),
                # The correction bias of the choice. Small and not zero: the
                # choice it gives is not the plain top-k of the scores.
                "router_bias": 0.02 * jax.random.normal(ks[6], (cfg.n_experts,), cfg.param_dtype),
                "shared_gate": norm(ks[7], D, F, fan_in=D),
                "shared_up": norm(ks[8], D, F, fan_in=D),
                "shared_down": norm(ks[9], F, D, fan_in=F),
                "w_gate": norm(ks[10], E, D, F, fan_in=D),
                "w_up": norm(ks[11], E, D, F, fan_in=D),
                "w_down": norm(ks[12], E, F, D, fan_in=F),
            }

        params = {
            # The head. Named so that it stands before ``embed`` in the
            # tree's order, as :mod:`.ouro` has it and for its reason: the
            # two are the state's largest leaves, the codec policy samples
            # the first of a take's largest, and an embedding's Adam
            # moments are zero in every row whose token was not yet seen.
            "decode": norm(keys[1], D, V, fan_in=D),
            "embed": norm(keys[0], V, D, fan_in=D),
            "layers": {
                layer_name(i): layer(keys[3 + i], dense=i < cfg.n_dense_layers)
                for i in range(cfg.n_layers)
            },
            "ln_f": ones(D),
        }
        if cfg.n_mtp:
            ks = jax.random.split(keys[2], 2)
            params["mtp"] = {
                "block": layer(ks[0], dense=False),
                "eh_proj": norm(ks[1], 2 * D, D, fan_in=2 * D),
                "ln_e": ones(D),
                "ln_f": ones(D),  # the shared head's own norm
                "ln_h": ones(D),
            }
        return params

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
        """``CE_main + mtp_weight * CE_mtp`` over the held vocabulary rows:
        the mean next-token cross-entropy of the main stack (the last
        position predicts nothing) and the module's mean cross-entropy
        against the token after the next (the last two predict nothing).
        ``mesh`` is unused: the signature is :meth:`Transformer.loss`'s,
        for ``make_train_step``."""
        cfg = self.config
        # The residual stream stays float32 (products read it in
        # ``cfg.dtype``): rounding it a layer would move the next router's
        # scores, and a top-k choice flips on a near tie.
        x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
        for i in range(cfg.n_layers):
            x = self._checkpointed_layer(params["layers"][layer_name(i)], x, None)
        h = _rmsnorm(x, params["ln_f"])
        total = self._blocked_nll(h.astype(cfg.dtype), params["decode"], tokens, ahead=1)
        if cfg.n_mtp:
            total = total + cfg.mtp_weight * self._mtp_nll(params, h, tokens)
        return total

    def _mtp_nll(self, params: Params, h: jax.Array, tokens: jax.Array) -> jax.Array:
        cfg = self.config
        mp = params["mtp"]
        with jax.named_scope("mtp.combine"):
            # Position i joins the main stack's output there with the
            # embedding of token i + 1.
            e = jnp.take(params["embed"], _tokens_ahead(tokens, 1), axis=0).astype(jnp.float32)
            both = jnp.concatenate([_rmsnorm(e, mp["ln_e"]), _rmsnorm(h, mp["ln_h"])], axis=-1)
            z = jnp.einsum(
                "bsd,dz->bsz", both.astype(cfg.dtype), mp["eh_proj"].astype(cfg.dtype),
                preferred_element_type=jnp.float32,
            )
        z = self._checkpointed_layer(mp["block"], z, "mtp.block")
        with jax.named_scope("mtp.head"):
            z = _rmsnorm(z, mp["ln_f"]).astype(cfg.dtype)
            return self._blocked_nll(z, params["decode"], tokens, ahead=2)

    def _checkpointed_layer(self, lp: Params, x: jax.Array, scope: Optional[str]) -> jax.Array:
        # Each layer is recomputed in the backward: only its input stays
        # alive across the step.
        return jax.checkpoint(self._layer, static_argnums=(2,))(lp, x, scope)

    def _layer(self, lp: Params, x: jax.Array, scope: Optional[str]) -> jax.Array:
        # ``scope`` is entered here, inside the checkpointed function: what
        # this layer (and an attention block inside it) recomputes is named
        # from this body, not from its caller's.
        cfg = self.config
        with jax.named_scope(scope) if scope else contextlib.nullcontext():
            x = x + self._attention(lp, _rmsnorm(x, lp["ln1"]).astype(cfg.dtype))
            u = _rmsnorm(x, lp["ln2"])
            if "router" not in lp:
                return x + _swiglu(u.astype(cfg.dtype), lp, "w_gate", "w_up", "w_down", cfg.dtype)
            with jax.named_scope("shared.expert"):
                shared = _swiglu(
                    u.astype(cfg.dtype), lp, "shared_gate", "shared_up", "shared_down", cfg.dtype)
            return x + shared + self.routed(lp, u)

    def _attention(self, lp: Params, a: jax.Array) -> jax.Array:
        cfg = self.config
        b, s, _ = a.shape
        H = cfg.n_heads
        with jax.named_scope("latent.proj"):
            cq = jnp.einsum("bsd,dr->bsr", a, lp["wq_a"].astype(cfg.dtype),
                            preferred_element_type=jnp.float32)
            cq = _rmsnorm(cq, lp["ln_q"]).astype(cfg.dtype)
            q = jnp.einsum("bsr,rz->bsz", cq, lp["wq_b"].astype(cfg.dtype),
                           preferred_element_type=jnp.float32)
            q = q.reshape(b, s, H, cfg.d_nope + cfg.d_rope)
            kv = jnp.einsum("bsd,dr->bsr", a, lp["wkv_a"].astype(cfg.dtype),
                            preferred_element_type=jnp.float32)
            c = _rmsnorm(kv[..., :cfg.kv_rank], lp["ln_kv"]).astype(cfg.dtype)
        # RoPE in float32 on the products' float32 results; rounded after.
        q = jnp.concatenate(
            [q[..., :cfg.d_nope], _rope(q[..., cfg.d_nope:], cfg.rope_theta)], axis=-1
        ).astype(cfg.dtype)
        k_rope = _rope(kv[:, :, None, cfg.kv_rank:], cfg.rope_theta)[:, :, 0, :].astype(cfg.dtype)
        out = latent_attention(
            q, c, k_rope, lp["wkv_b"].astype(cfg.dtype), d_nope=cfg.d_nope, q_block=cfg.q_block)
        return jnp.einsum(
            "bsz,zd->bsd", out.reshape(b, s, H * cfg.d_v), lp["wo"].astype(cfg.dtype),
            preferred_element_type=jnp.float32,
        )

    def route(self, lp: Params, u: jax.Array):
        """``(chosen, weights)``, each ``[tokens, top_k]``: the experts a
        token goes to, by the largest of ``sigmoid(u W_r) + b``, and its
        weight on each, the chosen scores (without ``b``) over their sum,
        times ``routed_scale``. The bias takes part in the choice alone."""
        cfg = self.config
        scores = jax.nn.sigmoid(jnp.matmul(
            u.astype(jnp.float32), lp["router"].astype(jnp.float32),
            precision=lax.Precision.HIGHEST,
        ))
        biased = lax.stop_gradient(scores + lp["router_bias"].astype(jnp.float32))
        _, chosen = lax.top_k(biased, cfg.top_k)
        picked = jnp.take_along_axis(scores, chosen, axis=-1)
        weights = picked / jnp.sum(picked, axis=-1, keepdims=True) * cfg.routed_scale
        return chosen, weights

    def routed(self, lp: Params, u: jax.Array) -> jax.Array:
        """This share's part of the routed experts' output: for every token
        ``sum over e chosen and held here of w_e * down_e(silu(gate_e u) *
        up_e u)``. The weights are normalised over all ``top_k`` chosen,
        held here or not."""
        cfg = self.config
        shape = u.shape
        u = u.reshape(-1, shape[-1])
        k, held = cfg.top_k, cfg.n_held_experts
        with jax.named_scope("moe.route"):
            chosen, weights = self.route(lp, u)
            weights = weights.reshape(-1)
            # A pair's expert as this share numbers it; `held` for a pair
            # whose expert lives elsewhere, so that it sorts last.
            local = chosen.reshape(-1) - cfg.first_expert
            local = jnp.where((local >= 0) & (local < held), local, held)
            order = jnp.argsort(local)  # stable: pairs grouped by expert
            back = jnp.argsort(order)  # where each pair went
            sizes = jnp.sum(local[:, None] == jnp.arange(held), axis=0, dtype=jnp.int32)
        # A row past the last group belongs to no expert held here; a
        # grouped product leaves such rows of its result as it finds them,
        # so they are cut off going in and after every product (see
        # :meth:`.smallthinker.SmallThinker.experts`).
        routed = (jnp.arange(order.size) < jnp.sum(sizes))[:, None]

        def product(lhs, bank, **kw):
            return jnp.where(routed, lax.ragged_dot(lhs, lp[bank].astype(cfg.dtype), sizes, **kw), 0)

        with jax.named_scope("moe.experts"):
            xs = jnp.where(routed, _pairs_of_tokens(u.astype(cfg.dtype), order, back, k), 0)
            h = jax.nn.silu(product(xs, "w_gate")) * product(xs, "w_up")
            out = product(h, "w_down", preferred_element_type=jnp.float32)
            y = _tokens_of_pairs(out * weights[order][:, None], order, back, k)
        return y.reshape(shape)

    # ------------------------------------------------------------------ loss

    def _blocked_nll(
        self, x: jax.Array, decode: jax.Array, tokens: jax.Array, *, ahead: int
    ) -> jax.Array:
        """The mean over the positions that have one of ``-log p(token i +
        ahead | position i)``. Logits and their log-sum-exp for
        ``loss_block`` positions at a time, each block recomputed in the
        backward."""
        cfg = self.config
        b, s, d = x.shape
        block = min(cfg.loss_block, s)
        if s % block:
            raise ValueError(f"seq_len {s} is no multiple of loss_block {block}")
        n = s // block
        targets = _tokens_ahead(tokens, ahead)
        counted = (jnp.arange(s) < s - ahead).astype(jnp.float32)
        w = decode.astype(cfg.dtype)

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
        return total / (b * (s - ahead))


def _tokens_ahead(tokens, ahead):
    """``tokens`` moved ``ahead`` places to the left; token 0 stands in where
    the sequence has ended (no position that counts reads it)."""
    return jnp.concatenate([tokens[:, ahead:], jnp.zeros_like(tokens[:, :ahead])], axis=1)


def _swiglu(u, lp, gate, up, down, dtype):
    """``(silu(u W_gate) * (u W_up)) W_down``, float32 out."""
    g = jnp.einsum("bsd,df->bsf", u, lp[gate].astype(dtype))
    h = jax.nn.silu(g) * jnp.einsum("bsd,df->bsf", u, lp[up].astype(dtype))
    return jnp.einsum("bsf,fd->bsd", h, lp[down].astype(dtype), preferred_element_type=jnp.float32)


def latent_attention(
    q: jax.Array, c: jax.Array, k_rope: jax.Array, w_kvb: jax.Array, *, d_nope: int, q_block: int
) -> jax.Array:
    """Causal softmax attention over keys and values rebuilt from a latent,
    ``q_block`` queries at a time. ``q`` is ``[batch, seq, heads, d_nope +
    d_rope]`` (its last ``d_rope`` channels rotated), ``c`` the normed
    latent ``[batch, seq, rank]``, ``k_rope`` the rotated key part that all
    heads share ``[batch, seq, d_rope]``, ``w_kvb`` ``[rank, heads * (d_nope
    + d_v)]``. In a block, ``c W_kvb`` gives each head's ``[k_nope; v]`` of
    the keys the block can see (a static slice), the head's key is
    ``[k_nope; k_rope]``, and the scores are scaled by ``(d_nope +
    d_rope) ** -0.5``. The block, its rebuilt keys and values included, is
    recomputed in the backward. Returns ``[batch, seq, heads, d_v]``."""
    b, s, heads, d_qk = q.shape

    def one(q0, qb, cb, rb, w):
        keys = cb.shape[1]
        with jax.named_scope("latent.proj"):
            kv = jnp.einsum("bsr,rz->bsz", cb, w).reshape(b, keys, heads, -1)
            rope = jnp.broadcast_to(rb[:, :, None, :], (b, keys, heads, rb.shape[-1]))
            k = jnp.concatenate([kv[..., :d_nope], rope], axis=-1)
            v = kv[..., d_nope:]
        with jax.named_scope("attn.global"):
            # Scores as ``[batch, heads, 1, queries, keys]``, the layout of
            # :func:`.smallthinker.blocked_attention` with every head a group
            # of one. As ``[batch, heads, queries, keys]`` the TPU compiler
            # turns the softmax's row maximum into a reduce-window over the
            # whole row (35 ms a block at 4 x 1024 x 8192: PERF.md 6, PR 46).
            qg = qb[:, :, :, None, :]
            scores = jnp.einsum(
                "bqhgd,bshd->bhgqs", qg, k, preferred_element_type=jnp.float32
            ) * d_qk ** -0.5
            qi = q0 + jnp.arange(qb.shape[1])[:, None]
            seen = jnp.arange(keys)[None, :] <= qi
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
            out = jnp.einsum("bhgqs,bshd->bqhgd", probs.astype(v.dtype), v)
            return out[:, :, :, 0, :]

    out = []
    for q0 in range(0, s, q_block):
        k1 = min(q0 + q_block, s)
        one_block = jax.checkpoint(functools.partial(one, q0))
        out.append(one_block(q[:, q0:k1], c[:, :k1], k_rope[:, :k1], w_kvb))
    return jnp.concatenate(out, axis=1)
