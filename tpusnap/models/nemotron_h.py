"""One chip's share of a hybrid state-space / sparse-expert decoder (the
``nemotron_h`` stack: Mamba-2 mixers, expert feed-forwards and a few
attention layers, **one mixer a layer**) — pure JAX.

What differs from :mod:`.joyai` and :mod:`.smallthinker`, and why it is a
module of its own:

- **A layer is one mixer, of three kinds, by a pattern.** ``h = h +
  Mixer_l(RMSNorm_l(h))`` with the mixer that the pattern's letter names:
  ``M`` a Mamba-2 mixer, ``E`` an expert feed-forward, ``*`` attention. One
  subtree a layer, as :mod:`.smallthinker` has it, each holding the leaves
  of its kind alone.
- **A Mamba-2 mixer that is told which heads and groups it holds.** With
  ``H`` heads of ``P`` channels held and ``G`` groups of ``N`` state
  elements: ``[z (H P); xBC (H P + 2 G N); dt (H)] = u W_in``; ``xBC =
  silu(conv(xBC) + b_conv)``, the convolution causal, depthwise, over
  ``conv_kernel`` positions; ``xBC`` splits into ``x`` (``H x P``), ``B``
  and ``C`` (``G x N`` each; head ``h`` reads group ``h // (H / G)``); ``dt =
  softplus(dt + dt_bias)``; ``A = -exp(A_log)``, one scalar a head; per head
  ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T`` and ``y_t = S_t C_t + D
  x_t``, by :func:`tpusnap.ops.ssd_scan`; ``y = GroupRMSNorm(y * silu(z)) *
  w`` over groups of ``H P / G`` channels (the gate before the norm); ``out =
  y W_out``. A share of whole groups computes, from its columns of ``W_in``
  and its rows of ``W_out``, its part of the mixer's output: the parts of
  all shares add up to the whole mixer's.
- **Experts of two matrices with a squared ReLU.** A routed expert is
  ``relu(u W_up)^2 W_down``; the router is :mod:`.joyai`'s (sigmoid scores, a
  correction bias in the choice alone, weights renormalised over all chosen
  and scaled); this share holds experts ``first_expert .. first_expert +
  n_held_experts`` and adds their part only: every held expert over every
  token, the router's weight (zero where the token did not choose it) on
  its hidden units, so no token is dropped at any imbalance and the time
  does not follow the routing; the shared expert, the same form at its own
  width, is added unweighted.
- **Grouped-query attention with no position term.** No rotary and nothing
  else: the mixers before it carry order.

The model keeps :class:`~.transformer.Transformer`'s surface (``init``,
``param_specs``, ``loss(params, tokens, mesh=...)``, ``config``), so
``make_train_step``, ``init_train_state`` and ``train_state_shardings``
serve it as they are. Products take bf16 operands and accumulate in f32;
the parameters, the residual stream, the norms, the router, the
convolution, the decays and the state between chunks are f32. Every layer
and every attention query block is recomputed in the backward; the
cross-entropy runs ``loss_block`` positions at a time.

Named scopes, for the traces: ``ssm.proj`` (``W_in``, ``W_out``, the gated
norm), ``ssm.conv``, ``ssm.scan`` (``dt``, the decays, everything of the
chunked scan and the skip term), ``attn.global``, ``moe.route`` /
``moe.experts`` and ``shared.expert`` as :mod:`.joyai` has them.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.ssd_scan import ssd_scan
from .joyai import JoyAI
from .smallthinker import blocked_attention, layer_name
from .transformer import _rmsnorm

Params = Dict[str, Any]

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 16384  # the rows of the vocabulary held here
    d_model: int = 2688
    pattern: str = "EMEMEM*"  # one mixer a layer: M Mamba-2, E experts, * attention
    ssm_heads: int = 8  # Mamba-2 heads held here
    ssm_head_dim: int = 64
    ssm_groups: int = 1  # groups held here: whole ones
    ssm_state: int = 128
    conv_kernel: int = 4
    chunk: int = 128  # positions of the scan's matrix products
    dt_min: float = 1e-3  # time_step_min, time_step_max, time_step_floor: the
    dt_max: float = 0.1  # range ``dt_bias`` is initialised to
    dt_floor: float = 1e-4
    d_expert: int = 1856  # a routed expert's width
    d_shared: int = 3712  # the shared expert's
    n_experts: int = 128  # the router's outputs
    top_k: int = 6
    first_expert: int = 0  # the experts held here: first .. first + n_held
    n_held_experts: int = 8
    routed_scale: float = 2.5  # routed_scaling_factor
    n_heads: int = 4  # query heads held here
    n_kv_heads: int = 1  # KV heads held here
    head_dim: int = 128
    norm_eps: float = 1e-5
    q_block: int = 1024  # queries an attention block holds
    loss_block: int = 1024  # positions whose logits are alive at once
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    use_ring_attention: bool = False  # token_sharding reads it; not offered here

    def __post_init__(self) -> None:
        if not self.pattern or set(self.pattern) - {MAMBA, EXPERTS, ATTENTION}:
            raise ValueError("the pattern names a mixer a layer: M, E or *")
        if self.ssm_heads % self.ssm_groups:
            raise ValueError("a share of the mixer holds whole groups of heads")
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_heads must be a multiple of n_kv_heads")
        if not 0 <= self.first_expert <= self.n_experts - self.n_held_experts:
            raise ValueError("the held experts must lie among the router's outputs")
        if self.use_ring_attention:
            raise ValueError("this model has no ring attention")

    @property
    def ssm_width(self) -> int:  # the mixer's inner channels held here
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_width(self) -> int:  # the channels the convolution runs over: x, B, C
        return self.ssm_width + 2 * self.ssm_groups * self.ssm_state


class NemotronH:
    """Functional model: ``init`` → params pytree, ``loss`` → scalar."""

    def __init__(self, config: NemotronHConfig) -> None:
        self.config = config

    # ------------------------------------------------------------------ init

    def init(self, key: jax.Array) -> Params:
        cfg = self.config
        D, V, f32 = cfg.d_model, cfg.vocab_size, cfg.param_dtype
        keys = jax.random.split(key, 2 + len(cfg.pattern))

        def norm(k, *shape, fan_in):
            return jax.random.normal(k, shape, f32) * fan_in ** -0.5

        def ones(*shape):
            return jnp.ones(shape, f32)

        def mamba(k):
            ks = jax.random.split(k, 6)
            H, W, K = cfg.ssm_heads, cfg.ssm_width, cfg.conv_kernel
            # ``dt`` log-uniform in [dt_min, dt_max], floored; the bias is its
            # inverse softplus, so that softplus(dt_bias) is that ``dt``.
            step = jnp.exp(jax.random.uniform(ks[3], (H,), f32) * (
                math.log(cfg.dt_max) - math.log(cfg.dt_min)) + math.log(cfg.dt_min))
            step = jnp.maximum(step, cfg.dt_floor)
            return {
                "A_log": jnp.log(jax.random.uniform(ks[4], (H,), f32, 1.0, 16.0)),
                "D": ones(H),
                # As the public checkpoint holds a depthwise convolution:
                # [channels, 1, positions], the last tap on the current position.
                "conv_b": jax.random.uniform(ks[2], (cfg.conv_width,), f32, -1.0, 1.0) * K ** -0.5,
                "conv_w": jax.random.uniform(
                    ks[1], (cfg.conv_width, 1, K), f32, -1.0, 1.0) * K ** -0.5,
                "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                "in_proj": norm(ks[0], D, W + cfg.conv_width + H, fan_in=D),
                "ln": ones(D),
                "ln_gate": ones(W),
                "out_proj": norm(ks[5], W, D, fan_in=W),
            }

        def experts(k):
            ks = jax.random.split(k, 6)
            E, F, S = cfg.n_held_experts, cfg.d_expert, cfg.d_shared
            return {
                "ln": ones(D),
                "router": norm(ks[0], D, cfg.n_experts, fan_in=D),
                # The correction bias of the choice. Small and not zero: the
                # choice it gives is not the plain top-k of the scores.
                "router_bias": 0.02 * jax.random.normal(ks[1], (cfg.n_experts,), f32),
                "shared_down": norm(ks[3], S, D, fan_in=S),
                "shared_up": norm(ks[2], D, S, fan_in=D),
                "w_down": norm(ks[5], E, F, D, fan_in=F),
                "w_up": norm(ks[4], E, D, F, fan_in=D),
            }

        def attention(k):
            ks = jax.random.split(k, 4)
            q_width, kv_width = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
            return {
                "ln": ones(D),
                "wk": norm(ks[1], D, kv_width, fan_in=D),
                "wo": norm(ks[3], q_width, D, fan_in=q_width),
                "wq": norm(ks[0], D, q_width, fan_in=D),
                "wv": norm(ks[2], D, kv_width, fan_in=D),
            }

        make = {MAMBA: mamba, EXPERTS: experts, ATTENTION: attention}
        return {
            # The head. Named so that it stands before ``embed`` in the
            # tree's order, as :mod:`.ouro` has it and for its reason.
            "decode": norm(keys[1], D, V, fan_in=D),
            "embed": norm(keys[0], V, D, fan_in=D),
            "layers": {layer_name(i): make[kind](keys[2 + i])
                       for i, kind in enumerate(cfg.pattern)},
            "ln_f": ones(D),
        }

    # ------------------------------------------------------- sharding specs

    def param_specs(self) -> Params:
        """Every leaf replicated over the ("data", "fsdp", "tensor") mesh:
        this model IS one chip's share (its heads, groups, experts and
        vocabulary rows are already the slice a chip holds); a mesh of
        several chips runs it data-parallel."""
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
        # scores, and a top-k choice flips on a near tie.
        x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
        for i, kind in enumerate(cfg.pattern):
            # Each layer is recomputed in the backward: only its input
            # stays alive across the step.
            x = jax.checkpoint(self._layer, static_argnums=(2,))(
                params["layers"][layer_name(i)], x, kind)
        x = _rmsnorm(x, params["ln_f"], cfg.norm_eps).astype(cfg.dtype)
        return self._blocked_nll(x, params["decode"], tokens, ahead=1)

    def _layer(self, lp: Params, x: jax.Array, kind: str) -> jax.Array:
        mixer = {MAMBA: self.mamba, EXPERTS: self.experts, ATTENTION: self.attention}[kind]
        return x + mixer(lp, _rmsnorm(x, lp["ln"], self.config.norm_eps))

    def mamba(self, lp: Params, u: jax.Array) -> jax.Array:
        """This share's part of the Mamba-2 mixer's output, float32: the
        heads and groups held here, from their columns of ``W_in`` to their
        rows of ``W_out``. ``u`` is the layer's normed input, float32."""
        cfg = self.config
        b, s, _ = u.shape
        H, G, N, W = cfg.ssm_heads, cfg.ssm_groups, cfg.ssm_state, cfg.ssm_width
        with jax.named_scope("ssm.proj"):
            zxbcdt = jnp.einsum("bsd,dz->bsz", u.astype(cfg.dtype),
                                lp["in_proj"].astype(cfg.dtype),
                                preferred_element_type=jnp.float32)
            z, xbc, dt = jnp.split(zxbcdt, [W, W + cfg.conv_width], axis=-1)
        with jax.named_scope("ssm.conv"):
            xbc = jax.nn.silu(causal_conv(xbc, lp["conv_w"], lp["conv_b"]))
        with jax.named_scope("ssm.scan"):
            x = xbc[..., :W].reshape(b, s, H, cfg.ssm_head_dim)
            y = ssd_scan(
                x, jax.nn.softplus(dt + lp["dt_bias"]), -jnp.exp(lp["A_log"]),
                xbc[..., W:W + G * N].reshape(b, s, G, N), xbc[..., W + G * N:].reshape(b, s, G, N),
                chunk=cfg.chunk, dtype=cfg.dtype)
            y = (y + lp["D"][:, None] * x).reshape(b, s, W)
        with jax.named_scope("ssm.proj"):
            # The gate before the norm; the norm over each group's channels.
            gated = (y * jax.nn.silu(z)).reshape(b, s, G, W // G)
            y = _rmsnorm(gated, lp["ln_gate"].reshape(G, W // G), cfg.norm_eps).reshape(b, s, W)
            return jnp.einsum("bsz,zd->bsd", y.astype(cfg.dtype), lp["out_proj"].astype(cfg.dtype),
                              preferred_element_type=jnp.float32)

    def attention(self, lp: Params, u: jax.Array) -> jax.Array:
        """Causal grouped-query attention of the heads held here, with no
        position term of any kind."""
        cfg = self.config
        b, s, _ = u.shape
        a = u.astype(cfg.dtype)
        q = jnp.einsum("bsd,dz->bsz", a, lp["wq"].astype(cfg.dtype))
        k = jnp.einsum("bsd,dz->bsz", a, lp["wk"].astype(cfg.dtype))
        v = jnp.einsum("bsd,dz->bsz", a, lp["wv"].astype(cfg.dtype))
        with jax.named_scope("attn.global"):
            out = blocked_attention(
                q.reshape(b, s, cfg.n_heads, cfg.head_dim),
                k.reshape(b, s, cfg.n_kv_heads, cfg.head_dim),
                v.reshape(b, s, cfg.n_kv_heads, cfg.head_dim),
                window=None, q_block=cfg.q_block)
        return jnp.einsum(
            "bsz,zd->bsd", out.reshape(b, s, cfg.n_heads * cfg.head_dim),
            lp["wo"].astype(cfg.dtype), preferred_element_type=jnp.float32)

    def experts(self, lp: Params, u: jax.Array) -> jax.Array:
        """The shared expert, unweighted, beside this share's part of the
        routed experts."""
        with jax.named_scope("shared.expert"):
            shared = self.shared(lp, u)
        return shared + self.routed(lp, u)

    def shared(self, lp: Params, u: jax.Array) -> jax.Array:
        cfg = self.config
        h = jnp.einsum("bsd,df->bsf", u.astype(cfg.dtype), lp["shared_up"].astype(cfg.dtype),
                       preferred_element_type=jnp.float32)
        return jnp.einsum("bsf,fd->bsd", _relu2(h).astype(cfg.dtype),
                          lp["shared_down"].astype(cfg.dtype), preferred_element_type=jnp.float32)

    # The router is the latent-attention model's, at this model's ``top_k``
    # and ``routed_scale``: sigmoid scores over all ``n_experts``, the bias
    # ``lp["router_bias"]`` in the choice alone, the chosen scores over their
    # sum, scaled. So is the blocked cross-entropy (``loss_block``, ``dtype``).
    route = JoyAI.route
    _blocked_nll = JoyAI._blocked_nll

    def routed(self, lp: Params, u: jax.Array) -> jax.Array:
        """This share's part of the routed experts' output: for every token
        ``sum over e chosen and held here of w_e * relu(u W_up_e)^2
        W_down_e``. The weights are normalised over all ``top_k`` chosen,
        held here or not.

        Every held expert runs over every token, and the router's weight
        on it, zero where the token did not choose it, multiplies its
        hidden units: the held banks are one feed-forward of ``n_held x
        d_expert`` units whose units a token's choice switches on. No
        token is dropped at any imbalance, and the time does not follow
        the routing, which the sorted, grouped product's does (each routed
        tile of 512 pair rows costs it as much as a whole expert over 8192
        tokens costs here: PERF.md 6, PR 49)."""
        cfg = self.config
        shape = u.shape
        u = u.reshape(-1, shape[-1])
        with jax.named_scope("moe.route"):
            chosen, weights = self.route(lp, u)
            # [tokens, held]: a token's weight on each expert held here.
            local = chosen[..., None] - cfg.first_expert == jnp.arange(cfg.n_held_experts)
            on_held = jnp.sum(jnp.where(local, weights[..., None], 0.0), axis=1)
        with jax.named_scope("moe.experts"):
            h = jnp.einsum("td,edf->tef", u.astype(cfg.dtype), lp["w_up"].astype(cfg.dtype),
                           preferred_element_type=jnp.float32)
            h = (_relu2(h) * on_held[..., None]).astype(cfg.dtype)
            y = jnp.einsum("tef,efd->td", h, lp["w_down"].astype(cfg.dtype),
                           preferred_element_type=jnp.float32)
        return y.reshape(shape)


def _relu2(h):
    return jnp.square(jax.nn.relu(h))


def causal_conv(x: jax.Array, w: jax.Array, bias: jax.Array) -> jax.Array:
    """A depthwise causal convolution along the sequence: ``out_t = bias +
    sum_j w[:, 0, j] * x_{t - (K - 1) + j}``, positions before the first
    read as zero. ``x`` is ``[batch, seq, channels]``, ``w`` ``[channels, 1,
    K]`` (the last tap on the current position), ``bias`` ``[channels]``."""
    taps = w.shape[-1]
    seq = x.shape[1]
    padded = jnp.pad(x, [(0, 0), (taps - 1, 0), (0, 0)])
    out = bias
    for j in range(taps):
        out = out + padded[:, j:j + seq] * w[:, 0, j]
    return out
