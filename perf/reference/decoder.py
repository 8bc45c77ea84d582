"""Plain float32 reference of the decoder, its loss and its first Adam steps.

Imports nothing of the program. It follows the configuration file: pre-norm
decoder (RMSNorm with a scale), rotary over the whole head on interleaved
pairs, fused QKV, causal softmax attention, a 4*d non-gated tanh-GELU MLP,
untied embedding and head, next-token cross-entropy, Adam on float32
parameters. Every matrix product is float32 at ``highest`` precision. It
makes its own weights from the seed (normal * fan_in^-0.5, the keys in the
order the configuration's ``init`` names), so it takes nothing the program
has made.

``quant`` is the control, never the reference: the linear layers' inputs
and weights are rounded to 8 bits (scaled per token and per output column)
before each product, the precision step below the configuration's bf16
that a later change would be tempted by. ``"int8"`` rounds to 8-bit
integers, ``"fp8"`` to float8 e4m3.
"""

from __future__ import annotations

import statistics
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
ADAM = {"lr": 1e-3, "b1": 0.9, "b2": 0.999, "eps": 1e-8}
N_STEPS = 3


def sizes(config: Dict[str, Any]) -> Dict[str, int]:
    """The sizes the reference needs, from a configuration file's keys."""
    return {
        "vocab": int(config["vocab_size"]),
        "d": int(config["hidden_size"]),
        "heads": int(config["num_attention_heads"]),
        "layers": int(config["num_hidden_layers"]),
        "d_ff": int(config["intermediate_size"]),
        "theta": float(config["rotary_emb_base"]),
    }


def n_params(config: Dict[str, Any]) -> int:
    c = sizes(config)
    per_layer = 2 * c["d"] + 4 * c["d"] * c["d"] + 2 * c["d"] * c["d_ff"]
    return 2 * c["vocab"] * c["d"] + c["layers"] * per_layer + c["d"]


def train_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Forward + backward operations a token needs: 6 a matrix parameter
    (embedding lookup left out), plus causal attention's 6 * s * d a layer."""
    c = sizes(config)
    matrix = c["layers"] * (4 * c["d"] ** 2 + 2 * c["d"] * c["d_ff"]) + c["vocab"] * c["d"]
    return 6.0 * matrix + 6.0 * c["layers"] * seq_len * c["d"]


def init_params(key: jax.Array, c: Dict[str, int]) -> Dict[str, Any]:
    L, D, F, V = c["layers"], c["d"], c["d_ff"], c["vocab"]
    keys = jax.random.split(key, 8)

    def norm(k, *shape, fan_in):
        return jax.random.normal(k, shape, jnp.float32) * fan_in**-0.5

    return {
        "embed": norm(keys[0], V, D, fan_in=D),
        "layers": {
            "ln1": jnp.ones((L, D), jnp.float32),
            "ln2": jnp.ones((L, D), jnp.float32),
            "wqkv": norm(keys[1], L, D, 3 * D, fan_in=D),
            "wo": norm(keys[2], L, D, D, fan_in=D),
            "w1": norm(keys[5], L, D, F, fan_in=D),
            "w2": norm(keys[6], L, F, D, fan_in=F),
        },
        "ln_f": jnp.ones((D,), jnp.float32),
        "unembed": norm(keys[3], D, V, fan_in=D),
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


def _rmsnorm(x, scale):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6) * scale


def _rope(x, theta):
    b, s, h, d = x.shape
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).reshape(b, s, h, d)


def loss_fn(params, tokens, c: Dict[str, int], quant: Optional[str] = None):
    b, s = tokens.shape
    heads, dh = c["heads"], c["d"] // c["heads"]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, lp):
        h = _rmsnorm(x, lp["ln1"])
        q, k, v = jnp.split(_mm(h, lp["wqkv"], quant), 3, axis=-1)
        q, k, v = (t.reshape(b, s, heads, dh) for t in (q, k, v))
        q, k = _rope(q, c["theta"]), _rope(k, c["theta"])
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) * dh**-0.5
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision=HIGHEST)
        x = x + _mm(out.reshape(b, s, c["d"]), lp["wo"], quant)
        h = _rmsnorm(x, lp["ln2"])
        x = x + _mm(jax.nn.gelu(_mm(h, lp["w1"], quant)), lp["w2"], quant)
        return x, None

    x = params["embed"][tokens]
    # Rematerialised layer by layer, so that the float32 score matrices of
    # one layer at a time are alive and the reference fits beside nothing.
    x, _ = lax.scan(jax.checkpoint(layer), x, params["layers"])
    logits = _mm(_rmsnorm(x, params["ln_f"]), params["unembed"], quant)
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return nll.mean()


def _by_path(tree) -> Dict[str, Any]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {"/".join(str(getattr(p, "key", p)) for p in path): x for path, x in flat}


def _leaf_norms(tree) -> Dict[str, jax.Array]:
    return {k: jnp.sqrt(jnp.sum(jnp.square(x))) for k, x in _by_path(tree).items()}


def adam_step(params, mu, nu, tokens, step, c, quant=None, place=lambda tree: tree):
    """One Adam step: the new parameters and moments, the loss, and the
    gradient's norm by leaf. ``step`` counts from 1."""
    loss, grads = jax.value_and_grad(loss_fn)(params, tokens, c, quant)
    grads = place(grads)
    bc1 = 1.0 - ADAM["b1"] ** step.astype(jnp.float32)
    bc2 = 1.0 - ADAM["b2"] ** step.astype(jnp.float32)
    mu = jax.tree.map(lambda m, g: ADAM["b1"] * m + (1 - ADAM["b1"]) * g, mu, grads)
    nu = jax.tree.map(lambda n, g: ADAM["b2"] * n + (1 - ADAM["b2"]) * g * g, nu, grads)
    params = jax.tree.map(
        lambda p, m, n: p - ADAM["lr"] * (m / bc1) / (jnp.sqrt(n / bc2) + ADAM["eps"]),
        params, mu, nu,
    )
    return place(params), mu, nu, loss, _leaf_norms(grads)


def first_steps(
    key: jax.Array,
    tokens,
    c: Dict[str, int],
    quant: Optional[str] = None,
    place: Callable[[Any], Any] = lambda tree: tree,
) -> Dict[str, Any]:
    """The first ``len(tokens)`` Adam steps from seeded weights: each
    step's loss, the first gradient's norm by leaf, the first moment after
    one step (``(1 - b1)`` times the first gradient, fetched to the host
    leaf by leaf), and the norm by leaf of the parameters' change over all
    the steps. ``place`` may pin a tree's layout (a sharding constraint);
    it never changes a value. One program a step, the moments donated, so
    that it fits one chip."""
    jit = jax.jit
    params0 = jit(lambda k: place(init_params(k, c)))(key)
    zeros = jit(lambda t: place(jax.tree.map(jnp.zeros_like, t)))  # laid out as the parameters are
    step_fn = jit(
        lambda p, m, n, t, i: adam_step(p, m, n, t, i, c, quant, place),
        donate_argnums=(1, 2),
    )
    params, mu, nu = params0, zeros(params0), zeros(params0)
    losses, grad_norms, first_mu = [], None, None
    for i, batch in enumerate(tokens):
        params, mu, nu, loss, norms = step_fn(params, mu, nu, batch, jnp.int32(i + 1))
        if grad_norms is None:
            grad_norms, first_mu = norms, _by_path(jax.device_get(mu))
        losses.append(loss)
    delta_norms = jit(
        lambda a, b: _leaf_norms(jax.tree.map(lambda x, y: x - y, a, b))
    )(params, params0)
    return {"losses": losses, "grad_norms": grad_norms, "delta_norms": delta_norms,
            "first_mu": first_mu}


def worst_leaf_gap(got: Dict[str, float], want: Dict[str, float]) -> float:
    """The widest gap between a leaf's norm and the reference's, as a
    share of the reference's norm of that leaf or of the median leaf,
    whichever is larger (some gradients are all but zero)."""
    floor = statistics.median(want.values())
    return max(abs(got[k] - want[k]) / max(want[k], floor) for k in want)


def worst_leaf_diff(got: Dict[str, Any], want: Dict[str, Any]) -> float:
    """The first-order number: by the worst leaf, the norm of the difference
    between two trees' leaves as a share of ``want``'s norm of that leaf or
    of the median leaf, whichever is larger. ``want`` holds host arrays;
    each is put beside ``got``'s leaf (on its devices, in its layout) for
    the one subtraction, so that no second tree is alive on the device."""
    both = jax.jit(lambda g, w: (jnp.sqrt(jnp.sum(jnp.square(g - w))),
                                 jnp.sqrt(jnp.sum(jnp.square(w)))))
    diff, norm = {}, {}
    for k, w in want.items():
        g = got[k] if isinstance(got[k], jax.Array) else jnp.asarray(got[k])
        diff[k], norm[k] = (float(x) for x in both(g, jax.device_put(w, g.sharding)))
    floor = statistics.median(norm.values())
    return max(diff[k] / max(norm[k], floor) for k in want)
