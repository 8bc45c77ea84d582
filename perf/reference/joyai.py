"""Plain float32 reference of one chip's share of a latent-attention,
sparse-expert decoder with a multi-token-prediction module: its sizes, its
weights, its loss.

With ``N`` layers held of which the first ``K`` are dense, ``H`` heads held,
and no bias on any matrix:

- ``x = E[tokens]``. For layer ``l``: ``h1 = h + MLA_l(RMSNorm(h; ln1))``,
  ``h' = h1 + FFN_l(RMSNorm(h1; ln2))``.
- ``MLA(a)``: ``cq = RMSNorm(a W_qa; ln_q)``, ``q = cq W_qb``, per head
  ``[q_nope (d_nope); q_rope (d_rope)]``; ``[ckv (rank); k_rope (d_rope)] =
  a W_kva``, ``c = RMSNorm(ckv; ln_kv)``; ``c W_kvb`` gives per head
  ``[k_nope (d_nope); v (d_v)]``. RoPE on interleaved pairs (channel ``2i``
  with ``2i + 1``), positions from 0, on ``q_rope`` and on the one
  ``k_rope`` that all heads share; a head's key is ``[k_nope; k_rope]``;
  causal ``softmax(q k^T / sqrt(d_nope + d_rope)) v``; ``W_o`` from ``H x
  d_v``. Keys and values are written out per head: no absorbed form.
- ``FFN`` of a dense layer: ``(silu(u W_gate) * (u W_up)) W_down``. Of an
  expert layer: the shared expert, the same SwiGLU at the experts' width,
  added unweighted, plus ``sum over e chosen and held here of w_e *
  Expert_e(u)``. ``s = sigmoid(u W_r)`` over all the router's outputs; the
  ``top_k`` chosen are the largest of ``s + b`` (``b`` the correction bias:
  in the choice alone, so its gradient is zero); ``w_e = routed_scale * s_e
  / sum of s over all the chosen``, held here or not. What the experts held
  elsewhere would add is left out.
- After the last layer ``h = RMSNorm(x; ln_f)``; ``CE_main`` is the mean
  next-token cross-entropy of ``h W_head`` over positions ``0..S-2``.
- The multi-token-prediction module, over positions ``i = 0..S-2``: ``z_i =
  [RMSNorm(E[token i+1]; ln_e); RMSNorm(h_i; ln_h)] W_eh``, one more expert
  layer (its own leaves, positions from 0), ``RMSNorm(.; its ln_f)``, the
  same ``W_head``; ``CE_mtp`` is the mean cross-entropy of positions
  ``0..S-3`` against token ``i + 2``. The loss is ``CE_main + lambda *
  CE_mtp``.

Every product is float32 at ``highest`` precision; Python loops over layers,
a scan over the held experts (each over all tokens, weighed by its mask: no
grouped product), no kernel, cache, block or batching. Rematerialised where
one chip's memory asks for it at 1 x 8192 and nowhere else: every layer (the
float32 scores of 4 heads are 1.07 GB a layer and the probabilities as much
again) and each of the two heads with its cross-entropy (float32 logits of
8192 x 16,160 are 0.53 GB, their log-softmax as much again). Attention
needs no blocks: a layer's scores fit whole. Imports nothing of the
program; it makes its own weights from the seed.

``quant`` is the control, never the reference: the inputs and weights of
the linear layers (the four low-rank projections, o, every SwiGLU, the
expert banks, ``W_eh``, the head) are rounded to 8 bits (scaled per token
and per output column) before each product; ``"int8"`` rounds to 8-bit
integers, ``"fp8"`` to float8 e4m3. The router stays in float32, as the
configuration states it.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST


def sizes(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the reference needs, from a configuration file's keys."""
    if int(config["num_key_value_heads"]) != int(config["num_attention_heads"]):
        raise ValueError("latent attention groups no heads: as many KV heads as heads")
    if int(config["n_shared_experts"]) != 1 or int(config["moe_layer_freq"]) != 1:
        raise ValueError("one shared expert, and an expert layer after every dense one")
    if int(config["n_group"]) != 1 or int(config["topk_group"]) != 1:
        raise ValueError("the router has no group limit")
    if int(config["num_nextn_predict_layers"]) not in (0, 1):
        raise ValueError("one multi-token-prediction module, or none")
    return {
        "vocab": int(config["vocab_size"]),
        "d": int(config["hidden_size"]),
        "heads": int(config["num_attention_heads"]),
        "q_rank": int(config["q_lora_rank"]),
        "kv_rank": int(config["kv_lora_rank"]),
        "d_nope": int(config["qk_nope_head_dim"]),
        "d_rope": int(config["qk_rope_head_dim"]),
        "d_v": int(config["v_head_dim"]),
        "layers": int(config["num_hidden_layers"]),
        "dense": int(config["first_k_dense_replace"]),
        "f_dense": int(config["intermediate_size"]),
        "f": int(config["moe_intermediate_size"]),
        "router": int(config["moe_router_outputs"]),
        "held": int(config["n_routed_experts"]),
        "first": int(config["moe_first_expert"]),
        "top_k": int(config["num_experts_per_tok"]),
        "scale": float(config["routed_scaling_factor"]),
        "mtp": int(config["num_nextn_predict_layers"]),
        "lambda": float(config["assumed"]["mtp_lambda"]),
        "theta": float(config["rope_theta"]),
        "eps": float(config["rms_norm_eps"]),
    }


def _matrix_params(c: Dict[str, Any]) -> Dict[str, int]:
    d, h = c["d"], c["heads"]
    return {
        "attention": d * c["q_rank"] + c["q_rank"] * h * (c["d_nope"] + c["d_rope"])
        + d * (c["kv_rank"] + c["d_rope"]) + c["kv_rank"] * h * (c["d_nope"] + c["d_v"])
        + h * c["d_v"] * d,
        "dense": 3 * d * c["f_dense"],
        "router": d * c["router"],
        "expert": 3 * d * c["f"],
        "combine": 2 * d * d,
        "head": c["vocab"] * d,
    }


def n_params(config: Dict[str, Any]) -> int:
    """The parameters held: each once, though the module reads the embedding
    and the head a second time."""
    c = sizes(config)
    m = _matrix_params(c)
    attention = m["attention"] + c["q_rank"] + c["kv_rank"] + 2 * c["d"]  # and its four norms
    expert_layer = attention + m["router"] + c["router"] + (1 + c["held"]) * m["expert"]
    main = c["dense"] * (attention + m["dense"]) + (c["layers"] - c["dense"]) * expert_layer
    module = c["mtp"] * (expert_layer + m["combine"] + 3 * c["d"])
    return main + module + 2 * m["head"] + c["d"]


def state_bytes(config: Dict[str, Any]) -> int:
    """Float32 parameters and both Adam moments, 12 bytes a parameter, and
    the int32 step counter."""
    return 12 * n_params(config) + 4


def train_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Forward + backward operations a token needs. 6 a matrix parameter it
    meets: the low-rank projections, the dense SwiGLU, the router, the
    shared expert, the head (embedding lookup left out), and the routed work
    only: of its ``top_k`` experts the share ``held / router`` lives here on
    average. Attention: 6 * heads * (d_nope + d_rope + d_v) a (query, key)
    pair under the mask. The module meets its block, ``W_eh`` and the head
    once more, at ``seq_len - 1`` positions. Rebuilding keys and values a
    query block, like every recompute, is not counted."""
    c = sizes(config)
    m = _matrix_params(c)
    experts_met = 1 + c["top_k"] * c["held"] / c["router"]
    expert_layer = m["attention"] + m["router"] + experts_met * m["expert"]
    main = (c["dense"] * (m["attention"] + m["dense"])
            + (c["layers"] - c["dense"]) * expert_layer + m["head"])
    module = c["mtp"] * (expert_layer + m["combine"] + m["head"]) * (seq_len - 1) / seq_len
    per_pair = 6.0 * c["heads"] * (c["d_nope"] + c["d_rope"] + c["d_v"])
    pairs = c["layers"] * seq_len * (seq_len + 1) / 2 + c["mtp"] * (seq_len - 1) * seq_len / 2
    return 6.0 * (main + module) + per_pair * pairs / seq_len


def init_params(key: jax.Array, c: Dict[str, Any]) -> Dict[str, Any]:
    D, V, F, E, H = c["d"], c["vocab"], c["f"], c["held"], c["heads"]
    width = H * c["d_v"]
    keys = jax.random.split(key, 3 + c["layers"])

    def norm(k, *shape, fan_in):
        return jax.random.normal(k, shape, jnp.float32) * fan_in**-0.5

    def ones(*shape):
        return jnp.ones(shape, jnp.float32)

    def layer(k, dense):
        ks = jax.random.split(k, 13)
        attention = {
            "ln1": ones(D),
            "ln2": ones(D),
            "ln_kv": ones(c["kv_rank"]),
            "ln_q": ones(c["q_rank"]),
            "wq_a": norm(ks[0], D, c["q_rank"], fan_in=D),
            "wq_b": norm(ks[1], c["q_rank"], H * (c["d_nope"] + c["d_rope"]), fan_in=c["q_rank"]),
            "wkv_a": norm(ks[2], D, c["kv_rank"] + c["d_rope"], fan_in=D),
            "wkv_b": norm(ks[3], c["kv_rank"], H * (c["d_nope"] + c["d_v"]), fan_in=c["kv_rank"]),
            "wo": norm(ks[4], width, D, fan_in=width),
        }
        if dense:
            return {
                **attention,
                "w_gate": norm(ks[5], D, c["f_dense"], fan_in=D),
                "w_up": norm(ks[6], D, c["f_dense"], fan_in=D),
                "w_down": norm(ks[7], c["f_dense"], D, fan_in=c["f_dense"]),
            }
        return {
            **attention,
            "router": norm(ks[5], D, c["router"], fan_in=D),
            "router_bias": 0.02 * jax.random.normal(ks[6], (c["router"],), jnp.float32),
            "shared_gate": norm(ks[7], D, F, fan_in=D),
            "shared_up": norm(ks[8], D, F, fan_in=D),
            "shared_down": norm(ks[9], F, D, fan_in=F),
            "w_gate": norm(ks[10], E, D, F, fan_in=D),
            "w_up": norm(ks[11], E, D, F, fan_in=D),
            "w_down": norm(ks[12], E, F, D, fan_in=F),
        }

    params = {
        "decode": norm(keys[1], D, V, fan_in=D),
        "embed": norm(keys[0], V, D, fan_in=D),
        "layers": {f"{i:02d}": layer(keys[3 + i], dense=i < c["dense"])
                   for i in range(c["layers"])},
        "ln_f": ones(D),
    }
    if c["mtp"]:
        ks = jax.random.split(keys[2], 2)
        params["mtp"] = {
            "block": layer(ks[0], dense=False),
            "eh_proj": norm(ks[1], 2 * D, D, fan_in=2 * D),
            "ln_e": ones(D),
            "ln_f": ones(D),
            "ln_h": ones(D),
        }
    return params


def _round8(x, axis, quant):
    top = {"int8": 127.0, "fp8": 448.0}[quant]
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top
    scale = jnp.where(scale == 0, 1.0, scale)
    if quant == "int8":
        rounded = jnp.round(x / scale) * scale
    else:
        rounded = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + lax.stop_gradient(rounded - x)


def _mm(a, b, quant):
    if quant:
        a, b = _round8(a, -1, quant), _round8(b, 0, quant)
    return jnp.matmul(a, b, precision=HIGHEST)


def _rmsnorm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """Interleaved pairs: channel ``2i`` turns with channel ``2i + 1`` by the
    angle ``position * theta^(-2i / d)``; ``x`` is ``[b, s, heads, d]``."""
    b, s, h, d = x.shape
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).reshape(b, s, h, d)


def swiglu(u, gate, up, down, quant=None):
    return _mm(jax.nn.silu(_mm(u, gate, quant)) * _mm(u, up, quant), down, quant)


def latent_attention(a, lp, c: Dict[str, Any], quant: Optional[str] = None):
    """``MLA(a)`` of the module's docstring, every head's keys and values
    written out from the latent, every query against all keys under the mask."""
    b, s, _ = a.shape
    H, d_nope, d_rope = c["heads"], c["d_nope"], c["d_rope"]
    cq = _rmsnorm(_mm(a, lp["wq_a"], quant), lp["ln_q"], c["eps"])
    q = _mm(cq, lp["wq_b"], quant).reshape(b, s, H, d_nope + d_rope)
    q = jnp.concatenate([q[..., :d_nope], _rope(q[..., d_nope:], c["theta"])], axis=-1)
    kv = _mm(a, lp["wkv_a"], quant)
    latent = _rmsnorm(kv[..., :c["kv_rank"]], lp["ln_kv"], c["eps"])
    k_rope = _rope(kv[:, :, None, c["kv_rank"]:], c["theta"])  # one for all heads
    kv = _mm(latent, lp["wkv_b"], quant).reshape(b, s, H, d_nope + c["d_v"])
    k = jnp.concatenate(
        [kv[..., :d_nope], jnp.broadcast_to(k_rope, (b, s, H, d_rope))], axis=-1)
    v = kv[..., d_nope:]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) * (d_nope + d_rope) ** -0.5
    probs = jax.nn.softmax(jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision=HIGHEST)
    return _mm(out.reshape(b, s, H * c["d_v"]), lp["wo"], quant)


def route(u, lp, c: Dict[str, Any]):
    """``[..., router outputs]``: a token's weight on each expert, zero off
    its ``top_k``. The scores are sigmoids; the choice is by score plus
    bias; the weights are the chosen scores over their sum, times the
    scale."""
    s = jax.nn.sigmoid(jnp.matmul(u, lp["router"], precision=HIGHEST))
    biased = lax.stop_gradient(s + lp["router_bias"])
    kth = jnp.sort(biased, axis=-1)[..., -c["top_k"]][..., None]
    chosen = jnp.where(biased >= kth, s, 0.0)
    return c["scale"] * chosen / jnp.sum(chosen, axis=-1, keepdims=True)


def routed(u, lp, c: Dict[str, Any], quant: Optional[str] = None):
    """The held experts' part of the routed output: each over all tokens,
    weighed by the router's weight on it (zero where the token did not
    choose it), one expert after the other."""
    weight = route(u, lp, c)[..., c["first"]: c["first"] + c["held"]]

    def add(y, expert):
        gate, up, down, w = expert
        return y + w[..., None] * swiglu(u, gate, up, down, quant), None

    banks = (lp["w_gate"], lp["w_up"], lp["w_down"], jnp.moveaxis(weight, -1, 0))
    return lax.scan(add, jnp.zeros_like(u), banks)[0]


def layer(x, lp, c: Dict[str, Any], quant: Optional[str] = None):
    """One layer: latent attention, then the dense SwiGLU or the shared
    expert beside the held experts' part, as the layer's leaves say."""
    x = x + latent_attention(_rmsnorm(x, lp["ln1"], c["eps"]), lp, c, quant)
    u = _rmsnorm(x, lp["ln2"], c["eps"])
    if "router" not in lp:
        return x + swiglu(u, lp["w_gate"], lp["w_up"], lp["w_down"], quant)
    shared = swiglu(u, lp["shared_gate"], lp["shared_up"], lp["shared_down"], quant)
    return x + shared + routed(u, lp, c, quant)


def cross_entropy(x, decode, targets, quant: Optional[str] = None):
    """The mean of ``-log softmax(x W_head)[target]`` over ``x``'s positions."""
    logp = jax.nn.log_softmax(_mm(x, decode, quant), axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0].mean()


def loss_fn(params, tokens, c: Dict[str, Any], quant: Optional[str] = None):
    # Rematerialised: one layer's activations, one head's logits at a time.
    one_layer = jax.checkpoint(functools.partial(layer, c=c, quant=quant))
    head = jax.checkpoint(functools.partial(cross_entropy, quant=quant))
    x = params["embed"][tokens]
    for index in range(c["layers"]):
        x = one_layer(x, params["layers"][f"{index:02d}"])
    h = _rmsnorm(x, params["ln_f"], c["eps"])
    loss = head(h[:, :-1], params["decode"], tokens[:, 1:])
    if c["mtp"]:
        mp = params["mtp"]
        # Position i: the embedding of token i + 1 first, then the main
        # stack's output (after its final norm) at position i.
        e = params["embed"][tokens[:, 1:]]
        both = jnp.concatenate([_rmsnorm(e, mp["ln_e"], c["eps"]),
                                _rmsnorm(h[:, :-1], mp["ln_h"], c["eps"])], axis=-1)
        z = one_layer(_mm(both, mp["eh_proj"], quant), mp["block"])
        z = _rmsnorm(z, mp["ln_f"], c["eps"])
        loss = loss + c["lambda"] * head(z[:, :-1], params["decode"], tokens[:, 2:])
    return loss
