"""Plain float32 reference of one chip's share of a hybrid state-space /
sparse-expert decoder whose layers are one mixer each (``model_type``
``nemotron_h``): its sizes, its weights, its loss.

The layers follow ``hybrid_override_pattern``, a letter a layer: ``M`` a
Mamba-2 mixer, ``E`` an expert feed-forward, ``*`` attention. No bias on any
matrix; the convolution has one.

- ``x = E[tokens]``; for layer ``l``: ``h' = h + Mixer_l(RMSNorm(h; ln))``;
  after the last ``RMSNorm(.; ln_f)``, the head ``W_head`` (untied), and the
  mean next-token cross-entropy over positions ``0..S-2``.
- ``M``, with ``H`` heads of ``P`` channels held and ``G`` groups of ``N``
  state elements: ``[z (H P); xBC (H P + 2 G N); dt (H)] = u W_in``; ``xBC =
  silu(conv(xBC) + b_conv)`` with ``conv(v)_t = sum_j w[:, 0, j] v_{t - (K -
  1) + j}`` (causal, depthwise, positions before the first read as zero);
  ``xBC`` splits into ``x`` (``H x P``), ``B``, ``C`` (``G x N`` each; head
  ``h`` reads group ``h // (H / G)``); ``dt = softplus(dt + dt_bias)``; ``A =
  -exp(A_log)``; per head, from ``S = 0``: ``S_t = exp(dt_t A) S_{t-1} + dt_t
  x_t B_t^T`` and ``y_t = S_t C_t + D x_t``, **position by position** (a
  ``lax.scan`` over the sequence: the recurrence itself, no chunked form);
  ``y = GroupRMSNorm(y * silu(z)) * w`` over groups of ``H P / G`` channels
  (the gate before the norm); ``out = y W_out``.
- ``E``: the shared expert ``relu(u W_su)^2 W_sd``, unweighted, plus ``sum
  over e chosen and held here of w_e * relu(u W_up_e)^2 W_down_e``. ``s =
  sigmoid(u W_r)`` over all the router's outputs; the ``top_k`` chosen are
  the largest of ``s + b`` (``b`` the correction bias: in the choice alone,
  so its gradient is zero); ``w_e = routed_scale * s_e / sum of s over all
  the chosen``, held here or not. What the experts held elsewhere would add
  is left out.
- ``*``: ``q = a W_q`` (heads of ``head_dim``), ``k = a W_k``, ``v = a W_v``;
  query head ``h`` reads KV head ``h // (heads / kv_heads)``; causal
  ``softmax(q k^T / sqrt(head_dim)) v``; ``W_o``. No rotary and no other
  position term.

Every product is float32 at ``highest`` precision; Python loops over layers,
a scan over the held experts (each over all tokens, weighed by its mask: no
grouped product), no kernel, cache, block or batching. Rematerialised where
one chip's memory asks for it at 1 x 8192 and nowhere else: every layer (a
Mamba-2 mixer's backward keeps a state of ``H x P x N`` floats a position,
2.1 GB at 8 x 64 x 128 over 8192 positions; attention's float32 scores of 4
heads are 1.07 GB and the probabilities as much again) and the head with its
cross-entropy (float32 logits of 8192 x 16,384 are 0.54 GB, their
log-softmax as much again). Imports nothing of the program; it makes its own
weights from the seed.

``quant`` is the control, never the reference: the inputs and weights of the
linear layers (``W_in``, ``W_out``, q, k, v, o, the shared expert, the expert
banks, the head) are rounded to 8 bits (scaled per token and per output
column) before each product; ``"int8"`` rounds to 8-bit integers, ``"fp8"``
to float8 e4m3. The router, the convolution and the recurrence stay in
float32, as the configuration states them.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
KINDS = "ME*"


def sizes(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the reference needs, from a configuration file's keys."""
    pattern = str(config["hybrid_override_pattern"])
    if len(pattern) != int(config["num_hidden_layers"]) or set(pattern) - set(KINDS):
        raise ValueError("the pattern names one mixer (M, E or *) for each of the layers")
    if int(config["n_shared_experts"]) != 1:
        raise ValueError("one shared expert")
    if int(config["n_group"]) != 1 or int(config["topk_group"]) != 1:
        raise ValueError("the router has no group limit")
    if int(config["mamba_num_heads"]) % int(config["n_groups"]):
        raise ValueError("a share of the mixer holds whole groups of heads")
    if any(config[k] for k in ("attention_bias", "mamba_proj_bias", "mlp_bias", "use_bias")) or (
            not config["use_conv_bias"]):
        raise ValueError("no bias on any matrix, one on the convolution")
    if config["mlp_hidden_act"] != "relu2" or config["mamba_hidden_act"] != "silu":
        raise ValueError("experts of relu^2, a mixer of silu")
    return {
        "vocab": int(config["vocab_size"]),
        "d": int(config["hidden_size"]),
        "pattern": pattern,
        "ssm_heads": int(config["mamba_num_heads"]),
        "ssm_p": int(config["mamba_head_dim"]),
        "ssm_groups": int(config["n_groups"]),
        "ssm_n": int(config["ssm_state_size"]),
        "conv": int(config["conv_kernel"]),
        "dt_min": float(config["time_step_min"]),
        "dt_max": float(config["time_step_max"]),
        "dt_floor": float(config["time_step_floor"]),
        "f": int(config["moe_intermediate_size"]),
        "f_shared": int(config["moe_shared_expert_intermediate_size"]),
        "router": int(config["moe_router_outputs"]),
        "held": int(config["n_routed_experts"]),
        "first": int(config["moe_first_expert"]),
        "top_k": int(config["num_experts_per_tok"]),
        "scale": float(config["routed_scaling_factor"]),
        "heads": int(config["num_attention_heads"]),
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["head_dim"]),
        "eps": float(config["layer_norm_epsilon"]),
    }


def _widths(c: Dict[str, Any]) -> Dict[str, int]:
    inner = c["ssm_heads"] * c["ssm_p"]
    conv = inner + 2 * c["ssm_groups"] * c["ssm_n"]
    return {"inner": inner, "conv": conv, "in_proj": inner + conv + c["ssm_heads"]}


def _matrix_params(c: Dict[str, Any]) -> Dict[str, int]:
    d, w = c["d"], _widths(c)
    return {
        "mamba": d * w["in_proj"] + w["inner"] * d,
        "router": d * c["router"],
        "expert": 2 * d * c["f"],
        "shared": 2 * d * c["f_shared"],
        "attention": 2 * d * c["heads"] * c["head_dim"] + 2 * d * c["kv_heads"] * c["head_dim"],
        "head": c["vocab"] * d,
    }


def n_params(config: Dict[str, Any]) -> int:
    c = sizes(config)
    m, w = _matrix_params(c), _widths(c)
    layer = {
        # The convolution and its bias, dt_bias, A_log, D, the gated norm, the layer's norm.
        "M": m["mamba"] + w["conv"] * (c["conv"] + 1) + 3 * c["ssm_heads"] + w["inner"] + c["d"],
        "E": m["router"] + c["router"] + c["held"] * m["expert"] + m["shared"] + c["d"],
        "*": m["attention"] + c["d"],
    }
    return sum(layer[kind] for kind in c["pattern"]) + 2 * m["head"] + c["d"]


def state_bytes(config: Dict[str, Any]) -> int:
    """Float32 parameters and both Adam moments, 12 bytes a parameter, and
    the int32 step counter."""
    return 12 * n_params(config) + 4


def train_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Forward + backward operations a token needs. 6 a matrix parameter it
    meets: ``W_in`` and ``W_out``, the router, the shared expert, q, k, v, o,
    the head (embedding lookup left out), and the routed work only: of its
    ``top_k`` experts the share ``held / router`` lives here on average.
    Attention: 12 * heads * head_dim a (query, key) pair under the mask. The
    mixer's recurrence, as the recurrence states it: a position's update
    and read of a state of ``heads x channels x state`` elements are 2
    multiply-adds an element, 6 * 2 * heads * channels * state with the
    backward, and the convolution 6 * kernel a channel; what the chunked
    form spends on top (the products inside a chunk), like every recompute,
    is not counted."""
    c = sizes(config)
    m, w = _matrix_params(c), _widths(c)
    kinds = {kind: c["pattern"].count(kind) for kind in KINDS}
    matrices = (kinds["M"] * m["mamba"]
                + kinds["E"] * (m["router"] + m["shared"]
                                + c["top_k"] * c["held"] / c["router"] * m["expert"])
                + kinds["*"] * m["attention"] + m["head"])
    recurrence = kinds["M"] * (12.0 * c["ssm_heads"] * c["ssm_p"] * c["ssm_n"]
                               + 6.0 * c["conv"] * w["conv"])
    pairs = kinds["*"] * 12.0 * c["heads"] * c["head_dim"] * (seq_len + 1) / 2
    return 6.0 * matrices + recurrence + pairs


def init_params(key: jax.Array, c: Dict[str, Any]) -> Dict[str, Any]:
    D, V, w = c["d"], c["vocab"], _widths(c)
    keys = jax.random.split(key, 2 + len(c["pattern"]))

    def norm(k, *shape, fan_in):
        return jax.random.normal(k, shape, jnp.float32) * fan_in**-0.5

    def ones(*shape):
        return jnp.ones(shape, jnp.float32)

    def mamba(k):
        ks = jax.random.split(k, 6)
        H, K = c["ssm_heads"], c["conv"]
        step = jnp.exp(jax.random.uniform(ks[3], (H,), jnp.float32) * (
            math.log(c["dt_max"]) - math.log(c["dt_min"])) + math.log(c["dt_min"]))
        step = jnp.maximum(step, c["dt_floor"])
        return {
            "A_log": jnp.log(jax.random.uniform(ks[4], (H,), jnp.float32, 1.0, 16.0)),
            "D": ones(H),
            "conv_b": jax.random.uniform(ks[2], (w["conv"],), jnp.float32, -1.0, 1.0) * K**-0.5,
            "conv_w": jax.random.uniform(ks[1], (w["conv"], 1, K), jnp.float32, -1.0, 1.0) * K**-0.5,
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),  # softplus's inverse
            "in_proj": norm(ks[0], D, w["in_proj"], fan_in=D),
            "ln": ones(D),
            "ln_gate": ones(w["inner"]),
            "out_proj": norm(ks[5], w["inner"], D, fan_in=w["inner"]),
        }

    def experts(k):
        ks = jax.random.split(k, 6)
        E, F, S = c["held"], c["f"], c["f_shared"]
        return {
            "ln": ones(D),
            "router": norm(ks[0], D, c["router"], fan_in=D),
            "router_bias": 0.02 * jax.random.normal(ks[1], (c["router"],), jnp.float32),
            "shared_down": norm(ks[3], S, D, fan_in=S),
            "shared_up": norm(ks[2], D, S, fan_in=D),
            "w_down": norm(ks[5], E, F, D, fan_in=F),
            "w_up": norm(ks[4], E, D, F, fan_in=D),
        }

    def attention(k):
        ks = jax.random.split(k, 4)
        q_width, kv_width = c["heads"] * c["head_dim"], c["kv_heads"] * c["head_dim"]
        return {
            "ln": ones(D),
            "wk": norm(ks[1], D, kv_width, fan_in=D),
            "wo": norm(ks[3], q_width, D, fan_in=q_width),
            "wq": norm(ks[0], D, q_width, fan_in=D),
            "wv": norm(ks[2], D, kv_width, fan_in=D),
        }

    make = {"M": mamba, "E": experts, "*": attention}
    return {
        "decode": norm(keys[1], D, V, fan_in=D),
        "embed": norm(keys[0], V, D, fan_in=D),
        "layers": {f"{i:02d}": make[kind](keys[2 + i]) for i, kind in enumerate(c["pattern"])},
        "ln_f": ones(D),
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


def recurrence(x, dt, a, b, c):
    """``y_t = S_t C_t`` with ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``,
    ``S = 0`` before the first position, one position after the other. ``x``
    is ``[batch, seq, heads, channels]``, ``dt`` ``[batch, seq, heads]``,
    ``a`` ``[heads]``, ``b`` and ``c`` ``[batch, seq, heads, state]`` (each
    head's group's, written out)."""
    batch, _, heads, channels = x.shape

    def step(state, at):
        x_t, dt_t, b_t, c_t = at
        state = (jnp.exp(dt_t * a)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., :, None] * b_t[..., None, :])
        return state, jnp.sum(state * c_t[..., None, :], axis=-1)

    start = jnp.zeros((batch, heads, channels, b.shape[-1]), jnp.float32)
    _, y = lax.scan(step, start, tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1)


def mamba(u, lp, c: Dict[str, Any], quant: Optional[str] = None):
    """``M`` of the module's docstring, for the heads and groups held."""
    batch, seq, _ = u.shape
    H, P, G, N, K = c["ssm_heads"], c["ssm_p"], c["ssm_groups"], c["ssm_n"], c["conv"]
    inner = H * P
    zxbcdt = _mm(u, lp["in_proj"], quant)
    z, xbc, dt = zxbcdt[..., :inner], zxbcdt[..., inner:-H], zxbcdt[..., -H:]
    padded = jnp.pad(xbc, [(0, 0), (K - 1, 0), (0, 0)])
    conv = sum(padded[:, j:j + seq] * lp["conv_w"][:, 0, j] for j in range(K))
    xbc = jax.nn.silu(conv + lp["conv_b"])
    x = xbc[..., :inner].reshape(batch, seq, H, P)
    # Each head's group's B and C, written out a head.
    b = jnp.repeat(xbc[..., inner:inner + G * N].reshape(batch, seq, G, N), H // G, axis=2)
    cc = jnp.repeat(xbc[..., inner + G * N:].reshape(batch, seq, G, N), H // G, axis=2)
    dt = jax.nn.softplus(dt + lp["dt_bias"])
    y = recurrence(x, dt, -jnp.exp(lp["A_log"]), b, cc) + lp["D"][:, None] * x
    gated = (y.reshape(batch, seq, inner) * jax.nn.silu(z)).reshape(batch, seq, G, inner // G)
    gated = gated * lax.rsqrt(jnp.mean(gated * gated, axis=-1, keepdims=True) + c["eps"])
    return _mm(gated.reshape(batch, seq, inner) * lp["ln_gate"], lp["out_proj"], quant)


def relu2_mlp(u, up, down, quant=None):
    return _mm(jnp.square(jax.nn.relu(_mm(u, up, quant))), down, quant)


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
        up, down, w = expert
        return y + w[..., None] * relu2_mlp(u, up, down, quant), None

    banks = (lp["w_up"], lp["w_down"], jnp.moveaxis(weight, -1, 0))
    return lax.scan(add, jnp.zeros_like(u), banks)[0]


def experts(u, lp, c: Dict[str, Any], quant: Optional[str] = None):
    return relu2_mlp(u, lp["shared_up"], lp["shared_down"], quant) + routed(u, lp, c, quant)


def attention(a, lp, c: Dict[str, Any], quant: Optional[str] = None):
    """``*`` of the module's docstring: every query against all keys under
    the mask, no position term."""
    b, s, _ = a.shape
    heads, kv_heads, dh = c["heads"], c["kv_heads"], c["head_dim"]
    q = _mm(a, lp["wq"], quant).reshape(b, s, heads, dh)
    k = jnp.repeat(_mm(a, lp["wk"], quant).reshape(b, s, kv_heads, dh), heads // kv_heads, axis=2)
    v = jnp.repeat(_mm(a, lp["wv"], quant).reshape(b, s, kv_heads, dh), heads // kv_heads, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) * dh**-0.5
    probs = jax.nn.softmax(jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision=HIGHEST)
    return _mm(out.reshape(b, s, heads * dh), lp["wo"], quant)


MIXERS = {"M": mamba, "E": experts, "*": attention}


def layer(x, lp, kind: str, c: Dict[str, Any], quant: Optional[str] = None):
    return x + MIXERS[kind](_rmsnorm(x, lp["ln"], c["eps"]), lp, c, quant)


def cross_entropy(x, decode, targets, quant: Optional[str] = None):
    """The mean of ``-log softmax(x W_head)[target]`` over ``x``'s positions."""
    logp = jax.nn.log_softmax(_mm(x, decode, quant), axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0].mean()


def loss_fn(params, tokens, c: Dict[str, Any], quant: Optional[str] = None):
    # Rematerialised: one layer's activations, the head's logits, at a time.
    head = jax.checkpoint(functools.partial(cross_entropy, quant=quant))
    x = params["embed"][tokens]
    for index, kind in enumerate(c["pattern"]):
        one_layer = jax.checkpoint(functools.partial(layer, kind=kind, c=c, quant=quant))
        x = one_layer(x, params["layers"][f"{index:02d}"])
    h = _rmsnorm(x, params["ln_f"], c["eps"])
    return head(h[:, :-1], params["decode"], tokens[:, 1:])
