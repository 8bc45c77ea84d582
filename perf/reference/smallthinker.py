"""Plain float32 reference of one chip's share of a sparse-expert decoder
with window and global attention: its sizes, its weights, its loss.

The layer, for layer ``l`` with input ``h`` (no matrix has a bias):

- ``a = RMSNorm(h; ln1)``. Router, from the attention block's normed
  input: ``r = a W_r`` (all the router's outputs), ``T`` the ``top_k``
  largest, ``w`` the softmax over all outputs restricted to ``T`` and
  renormalised.
- ``q = a W_q``, ``k = a W_k``, ``v = a W_v``; query head ``i`` reads KV
  head ``i // (heads / kv_heads)``. Where ``rope_layout[l]`` is 1, ``q``
  and ``k`` are rotated over the whole head (interleaved pairs); where it
  is 0 there is no positional encoding. Causal; where
  ``sliding_window_layout[l]`` is 1, query ``i`` sees keys ``j`` with
  ``i - window < j <= i``. ``h1 = h + softmax(q k^T / sqrt(dh) + mask) v W_o``.
- ``b = RMSNorm(h1; ln2)``; ``y = sum over e in T and held here of
  w_e * W_down,e (relu(W_gate,e b) * W_up,e b)``; the layer gives
  ``h1 + y``. What the experts held elsewhere would add is left out.
- After the last layer: RMSNorm, logits over the held vocabulary rows,
  mean next-token cross-entropy.

Every product is float32 at ``highest`` precision. Attention runs in
query blocks, each recomputed in the backward, and every layer is
rematerialised, so that no ``seq x seq`` array of all heads is alive and
three steps at the full sequence fit one chip. Each held expert is
computed over all tokens and weighed by its mask. Blocks and experts are
loops (``lax.map``, ``lax.scan``), not unrolled: the float32 ``highest``
program of the unrolled form takes the TPU compiler three minutes. Imports nothing of the
program; it makes its own weights from the seed.

``quant`` is the control, never the reference: the inputs and weights of
the linear layers and of the expert products are rounded to 8 bits
(scaled per token and per output column) before each product; ``"int8"``
rounds to 8-bit integers, ``"fp8"`` to float8 e4m3. The router stays in
float32, as the configuration states it.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
Q_BLOCK = 1024  # queries a block of the reference's attention holds


def sizes(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the reference needs, from a configuration file's keys."""
    layers = int(config["num_hidden_layers"])
    return {
        "vocab": int(config["vocab_size"]),
        "d": int(config["hidden_size"]),
        "heads": int(config["num_attention_heads"]),
        "kv_heads": int(config["num_key_value_heads"]),
        "dh": int(config["head_dim"]),
        "layers": layers,
        "f": int(config["moe_ffn_hidden_size"]),
        "router": int(config["moe_router_outputs"]),
        "held": int(config["moe_num_primary_experts"]),
        "first": int(config["moe_first_expert"]),
        "top_k": int(config["moe_num_active_primary_experts"]),
        "window": int(config["sliding_window_size"]),
        "theta": float(config["rope_theta"]),
        "eps": float(config["rms_norm_eps"]),
        "rope": tuple(int(x) for x in config["rope_layout"][:layers]),
        "windowed": tuple(int(x) for x in config["sliding_window_layout"][:layers]),
    }


def _matrix_params(c: Dict[str, Any]) -> Dict[str, int]:
    q, kv = c["heads"] * c["dh"], c["kv_heads"] * c["dh"]
    return {
        "attention": 2 * c["d"] * q + 2 * c["d"] * kv,
        "router": c["d"] * c["router"],
        "expert": 3 * c["d"] * c["f"],
        "head": c["vocab"] * c["d"],
    }


def n_params(config: Dict[str, Any]) -> int:
    c = sizes(config)
    m = _matrix_params(c)
    per_layer = m["attention"] + m["router"] + 2 * c["d"] + c["held"] * m["expert"]
    return c["layers"] * per_layer + 2 * m["head"] + c["d"]


def state_bytes(config: Dict[str, Any]) -> int:
    """Float32 parameters and both Adam moments, 12 bytes a parameter, and
    the int32 step counter."""
    return 12 * n_params(config) + 4


def train_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Forward + backward operations a token needs. 6 a matrix parameter
    it meets: the attention projections, the router, the head (embedding
    lookup left out), and the routed work only: of its ``top_k`` experts
    the share ``held / router`` lives here on average. Attention: 12 *
    heads * head_dim a (query, key) pair the mask lets through."""
    c = sizes(config)
    m = _matrix_params(c)
    experts_met = c["top_k"] * c["held"] / c["router"]
    matrix = c["layers"] * (m["attention"] + m["router"] + experts_met * m["expert"]) + m["head"]
    pairs = 0.0
    for windowed in c["windowed"]:
        w = min(c["window"], seq_len) if windowed else seq_len
        pairs += w * (w + 1) / 2 + (seq_len - w) * w
    return 6.0 * matrix + 12.0 * c["heads"] * c["dh"] * pairs / seq_len


def init_params(key: jax.Array, c: Dict[str, Any]) -> Dict[str, Any]:
    D, V, F, E = c["d"], c["vocab"], c["f"], c["held"]
    q, kv = c["heads"] * c["dh"], c["kv_heads"] * c["dh"]
    keys = jax.random.split(key, 2 + c["layers"])

    def norm(k, *shape, fan_in):
        return jax.random.normal(k, shape, jnp.float32) * fan_in**-0.5

    def layer(k):
        ks = jax.random.split(k, 8)
        return {
            "ln1": jnp.ones((D,), jnp.float32),
            "ln2": jnp.ones((D,), jnp.float32),
            "router": norm(ks[0], D, c["router"], fan_in=D),
            "wq": norm(ks[1], D, q, fan_in=D),
            "wk": norm(ks[2], D, kv, fan_in=D),
            "wv": norm(ks[3], D, kv, fan_in=D),
            "wo": norm(ks[4], q, D, fan_in=q),
            "w_gate": norm(ks[5], E, D, F, fan_in=D),
            "w_up": norm(ks[6], E, D, F, fan_in=D),
            "w_down": norm(ks[7], E, F, D, fan_in=F),
        }

    return {
        "embed": norm(keys[0], V, D, fan_in=D),
        "layers": {f"{i:02d}": layer(keys[2 + i]) for i in range(c["layers"])},
        "ln_f": jnp.ones((D,), jnp.float32),
        "unembed": norm(keys[1], D, V, fan_in=D),
    }


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
    b, s, h, d = x.shape
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).reshape(b, s, h, d)


def attention(q, k, v, window: Optional[int]):
    """``softmax(q k^T / sqrt(dh) + mask) v`` with ``q`` ``[b, s, heads,
    dh]`` and ``k``, ``v`` ``[b, s, kv_heads, dh]``, one block of queries
    after the other against all the keys under the mask."""
    b, s, heads, dh = q.shape
    k, v = (jnp.repeat(t, heads // t.shape[2], axis=2) for t in (k, v))
    block = min(Q_BLOCK, s)
    assert s % block == 0, (s, block)
    j = jnp.arange(s)[None, :]

    @jax.checkpoint
    def rows(first, qb):
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k, precision=HIGHEST) * dh**-0.5
        i = first + jnp.arange(block)[:, None]
        mask = j <= i if window is None else (j <= i) & (i - window < j)
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision=HIGHEST)

    blocks = q.reshape(b, s // block, block, heads, dh).swapaxes(0, 1)
    out = lax.map(lambda args: rows(*args), (jnp.arange(0, s, block), blocks))
    return out.swapaxes(0, 1).reshape(b, s, heads, dh)


def route(a, router, c):
    """``[tokens, router outputs]``: a token's weight on each expert, zero
    off its ``top_k``; the softmax over all outputs, renormalised over the
    chosen ones."""
    r = jnp.matmul(a, router, precision=HIGHEST)
    kth = jnp.sort(r, axis=-1)[..., -c["top_k"]][..., None]
    e = jnp.where(r >= kth, jnp.exp(r - jnp.max(r, axis=-1, keepdims=True)), 0.0)
    return e / jnp.sum(e, axis=-1, keepdims=True)


def experts(lp, a, b, c, quant=None):
    """The held experts' part of the expert layer's output: each over all
    tokens, weighed by the router's weight on it (zero where the token did
    not choose it), one expert after the other."""
    weight = route(a, lp["router"], c)[..., c["first"] : c["first"] + c["held"]]

    def add(y, expert):
        gate, up, down, w = expert
        h = jax.nn.relu(_mm(b, gate, quant)) * _mm(b, up, quant)
        return y + w[..., None] * _mm(h, down, quant), None

    banks = (lp["w_gate"], lp["w_up"], lp["w_down"], jnp.moveaxis(weight, -1, 0))
    return lax.scan(add, jnp.zeros_like(b), banks)[0]


def layer(x, lp, index: int, c: Dict[str, Any], quant: Optional[str] = None):
    """Layer ``index`` of the period: attention (global or windowed, with
    or without RoPE, as the layouts say), then the held experts' part."""
    b, s, _ = x.shape
    a = _rmsnorm(x, lp["ln1"], c["eps"])
    q = _mm(a, lp["wq"], quant).reshape(b, s, c["heads"], c["dh"])
    k = _mm(a, lp["wk"], quant).reshape(b, s, c["kv_heads"], c["dh"])
    v = _mm(a, lp["wv"], quant).reshape(b, s, c["kv_heads"], c["dh"])
    if c["rope"][index]:
        q, k = _rope(q, c["theta"]), _rope(k, c["theta"])
    out = attention(q, k, v, c["window"] if c["windowed"][index] else None)
    x = x + _mm(out.reshape(b, s, c["heads"] * c["dh"]), lp["wo"], quant)
    return x + experts(lp, a, _rmsnorm(x, lp["ln2"], c["eps"]), c, quant)


def loss_fn(params, tokens, c: Dict[str, Any], quant: Optional[str] = None):
    x = params["embed"][tokens]
    for index in range(c["layers"]):
        # Rematerialised layer by layer: one layer's activations at a time.
        one = functools.partial(layer, index=index, c=c, quant=quant)
        x = jax.checkpoint(one)(x, params["layers"][f"{index:02d}"])
    logits = _mm(_rmsnorm(x, params["ln_f"], c["eps"]), params["unembed"], quant)
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return nll.mean()
