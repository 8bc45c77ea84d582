"""Plain float32 reference of a looped decoder with an exit gate: its
sizes, its weights, its loss.

With ``N`` layers held, ``T`` passes (the source's ``total_ut_steps``),
and no bias on any matrix:

- ``x(0) = E[tokens]``. For pass ``t = 1..T``: ``h = x(t-1)``; for layer
  ``l = 1..N``, the same leaves in every pass, ``a = h + RMSNorm(Attn_l(
  RMSNorm(h; ln1)); ln2)``, ``h = a + RMSNorm(MLP_l(RMSNorm(a; ln3));
  ln4)``; then ``x(t) = RMSNorm(h; ln_f)``: the final norm closes every
  pass and its output is what the next pass reads.
- ``Attn(u)``: ``q, k, v = u W_q, u W_k, u W_v`` in ``heads`` heads (as
  many KV heads); RoPE over the whole head on rotate-half pairs (channel
  ``i`` with ``i + dh / 2``), positions ``0..S-1`` in every pass; causal
  ``softmax(q k^T / sqrt(dh)) v``; ``W_o``. ``MLP(u) = (silu(u W_gate) *
  (u W_up)) W_down``.
- After pass ``t``, per position: ``logits(t) = x(t) W_head``, ``CE(t)``
  the next-token cross-entropy, ``lambda(t) = sigmoid(x(t) . w_g + b_g)``
  (one gate for all passes). ``p(t) = lambda(t) prod_{j<t} (1 -
  lambda(j))`` for ``t < T``, ``p(T) = prod_{j<T} (1 - lambda(j))``.
- The loss: the mean over the positions that have a next token of
  ``sum_t p(t) CE(t) - beta H(p)``, ``H(p) = -sum_t p(t) log p(t)``.
  ``log p(t)`` is worked out as a sum of ``log sigmoid``s and ``p(t)`` as
  its exponential: one Adam step of 1e-3 moves a gate's logit by ten and
  more at these widths, and a gate that reads exactly 0 or 1 makes the
  product form's ``p log p`` a ``0 * log 0``.

Every product is float32 at ``highest`` precision; a Python loop over
passes and layers, no kernel, cache, block or batching. Rematerialised where
one chip's memory forces it at 1 x 4096 and nowhere else: every layer
application (16 of them, each with float32 scores of 16 heads, 1.07 GB, and
as much again for the probabilities) and each pass's head and cross-entropy
(float32 logits of 4096 x 49,152 are 0.8 GB a pass and their log-softmax as
much again: with the four heads' kept the compiler counts 17.5 GB for one
Adam step, over the chip's 16.9; as it stands 12.6 GB). Attention needs no
blocks: a layer application's scores fit whole. Imports nothing of the
program; it makes its own weights from the seed.

``quant`` is the control, never the reference: the inputs and weights of
the linear layers (q, k, v, o, gate, up, down, head) are rounded to 8 bits
(scaled per token and per output column) before each product; ``"int8"``
rounds to 8-bit integers, ``"fp8"`` to float8 e4m3. The exit gate stays in
float32, as the configuration states it.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST


def sizes(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the reference needs, from a configuration file's keys."""
    if int(config["num_key_value_heads"]) != int(config["num_attention_heads"]):
        raise ValueError("this architecture groups no heads: as many KV heads as heads")
    return {
        "vocab": int(config["vocab_size"]),
        "d": int(config["hidden_size"]),
        "heads": int(config["num_attention_heads"]),
        "dh": int(config["head_dim"]),
        "layers": int(config["num_hidden_layers"]),
        "f": int(config["intermediate_size"]),
        "passes": int(config["total_ut_steps"]),
        "theta": float(config["rope_theta"]),
        "eps": float(config["rms_norm_eps"]),
        "beta": float(config["assumed"]["beta"]),
    }


def _matrix_params(c: Dict[str, Any]) -> Dict[str, int]:
    width = c["heads"] * c["dh"]
    return {"layer": 4 * c["d"] * width + 3 * c["d"] * c["f"], "head": c["vocab"] * c["d"]}


def n_params(config: Dict[str, Any]) -> int:
    """The parameters held: each once, however often a pass uses it."""
    c = sizes(config)
    m = _matrix_params(c)
    gate = c["d"] + 1
    return c["layers"] * (m["layer"] + 4 * c["d"]) + 2 * m["head"] + c["d"] + gate


def state_bytes(config: Dict[str, Any]) -> int:
    """Float32 parameters and both Adam moments, 12 bytes a parameter, and
    the int32 step counter."""
    return 12 * n_params(config) + 4


def train_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Forward + backward operations a token needs: 6 a matrix parameter
    each time it is met (every layer in each of the passes, the head and
    the gate once a pass; embedding lookup left out), plus causal
    attention's 12 * heads * head_dim a (query, key) pair in every layer
    application. The recompute is not counted."""
    c = sizes(config)
    m = _matrix_params(c)
    matrix = c["passes"] * (c["layers"] * m["layer"] + m["head"] + c["d"])
    pairs = seq_len * (seq_len + 1) / 2
    attention = c["passes"] * c["layers"] * 12.0 * c["heads"] * c["dh"] * pairs / seq_len
    return 6.0 * matrix + attention


def init_params(key: jax.Array, c: Dict[str, Any]) -> Dict[str, Any]:
    L, D, F, V = c["layers"], c["d"], c["f"], c["vocab"]
    width = c["heads"] * c["dh"]
    keys = jax.random.split(key, 10)

    def norm(k, *shape, fan_in):
        return jax.random.normal(k, shape, jnp.float32) * fan_in**-0.5

    def ones(*shape):
        return jnp.ones(shape, jnp.float32)

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
        "decode": norm(keys[1], D, V, fan_in=D),
        "gate": {"w": norm(keys[9], D, fan_in=D), "b": jnp.zeros((1,), jnp.float32)},
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
    """Rotate-half pairs: channel ``i`` turns with channel ``i + dh / 2``
    by the angle ``position * theta^(-2i / dh)``."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(q, k, v):
    """Causal ``softmax(q k^T / sqrt(dh)) v`` with ``q``, ``k``, ``v``
    ``[b, s, heads, dh]``: every query against all the keys under the mask."""
    s, dh = q.shape[1], q.shape[3]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) * dh**-0.5
    probs = jax.nn.softmax(jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision=HIGHEST)


def layer(h, lp, c: Dict[str, Any], quant: Optional[str] = None):
    """One application of one layer: attention and MLP, each between two norms."""
    b, s, _ = h.shape
    shape = (b, s, c["heads"], c["dh"])
    u = _rmsnorm(h, lp["ln1"], c["eps"])
    q, k, v = (_mm(u, lp[w], quant).reshape(shape) for w in ("wq", "wk", "wv"))
    out = attention(_rope(q, c["theta"]), _rope(k, c["theta"]), v)
    out = _mm(out.reshape(b, s, c["heads"] * c["dh"]), lp["wo"], quant)
    a = h + _rmsnorm(out, lp["ln2"], c["eps"])
    u = _rmsnorm(a, lp["ln3"], c["eps"])
    out = _mm(jax.nn.silu(_mm(u, lp["w_gate"], quant)) * _mm(u, lp["w_up"], quant),
              lp["w_down"], quant)
    return a + _rmsnorm(out, lp["ln4"], c["eps"])


def cross_entropy(x, decode, tokens, quant: Optional[str] = None):
    """``[b, s - 1]``: each position's cross-entropy against the next token."""
    logp = jax.nn.log_softmax(_mm(x[:, :-1], decode, quant), axis=-1)
    return -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]


def log_exit_distribution(z):
    """``log p(t)`` for ``t = 1..T`` from the gates' logits ``z(1..T)`` (the
    last one unused): a pass is left with its gate's probability ``lambda =
    sigmoid(z)``, if no earlier pass was; the last pass takes what is left.
    In logarithms, ``log lambda(t) + sum_{j<t} log(1 - lambda(j))``: a gate
    that three Adam steps have saturated gives ``p = 0`` as a product, and
    ``p log p`` is then ``0 * log 0``."""
    log_stay, log_p = jnp.zeros_like(z[0]), []
    for zt in z[:-1]:
        log_p.append(jax.nn.log_sigmoid(zt) + log_stay)
        log_stay = log_stay + jax.nn.log_sigmoid(-zt)
    return [*log_p, log_stay]


def loss_fn(params, tokens, c: Dict[str, Any], quant: Optional[str] = None):
    x = params["embed"][tokens]
    ces, logits = [], []
    for _ in range(c["passes"]):
        h = x
        for index in range(c["layers"]):
            lp = jax.tree.map(lambda leaf: leaf[index], params["layers"])
            # Rematerialised: one layer application's activations at a time.
            h = jax.checkpoint(lambda h, lp: layer(h, lp, c, quant))(h, lp)
        x = _rmsnorm(h, params["ln_f"], c["eps"])
        # Rematerialised: one pass's logits at a time.
        ces.append(jax.checkpoint(lambda x, w: cross_entropy(x, w, tokens, quant))(
            x, params["decode"]))
        z = jnp.matmul(x, params["gate"]["w"], precision=HIGHEST) + params["gate"]["b"]
        logits.append(z[:, :-1])
    per_position = 0.0
    for log_p, ce in zip(log_exit_distribution(logits), ces):
        p = jnp.exp(log_p)
        per_position = per_position + p * ce + c["beta"] * p * log_p
    return per_position.mean()
