"""Pallas TPU flash attention (forward) for the flagship model.

The reference framework ships no kernels of its own (it is a
checkpointing library — SURVEY.md §2); this kernel exists because our
flagship model is a real TPU training workload and attention is its hot
op. Design follows the canonical TPU flash-attention shape:

- Grid ``(batch, heads, q_blocks, k_blocks)`` — the k-block axis is
  innermost and TPU grids execute sequentially, so the f32 accumulators
  (``acc``, running max ``m``, running sum ``l``) live in VMEM scratch
  and persist across k-steps of one q-block.
- Online softmax in f32 (MXU matmuls via ``jnp.dot`` with
  ``preferred_element_type``), output cast back to the input dtype.
- Causal masking at two granularities: whole k-blocks strictly above
  the diagonal are skipped with ``pl.when`` (no FLOPs, no VMEM traffic
  beyond the prefetch), and the diagonal blocks apply an elementwise
  ``broadcasted_iota`` mask.
- Head dim and sequence length are zero-padded to lane/tile multiples
  in the wrapper; padded *keys* are masked via a validity mask, padded
  *query* rows are sliced off on return.

Backward runs as a recomputing VJP on the reference formulation (XLA
fuses it well); a dedicated Pallas backward is a known follow-up.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_NEG_INF = -1e30


def _flash_fwd_kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    acc_ref,
    m_ref,
    l_ref,
    *,
    scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    s_valid: int,
):
    """One (batch, head, q_block, k_block) grid step."""
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def body():
        q = q_ref[0, 0].astype(jnp.float32)  # [bq, d]
        k = k_ref[0, 0].astype(jnp.float32)  # [bk, d]
        v = v_ref[0, 0].astype(jnp.float32)  # [bk, d]

        s = jax.lax.dot_general(
            q,
            k,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bq, bk]
        s = s * scale

        q_pos = iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        k_pos = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        mask = k_pos < s_valid  # padded keys contribute nothing
        if causal:
            mask = jnp.logical_and(mask, q_pos >= k_pos)
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[:, 0:1]  # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)  # [bq, 1]
        p = jnp.exp(s - m_new)  # [bq, bk]
        # Fully-masked rows: m_new == _NEG_INF and p == 1 — zero them.
        p = jnp.where(mask, p, 0.0)

        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p,
            v,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        # Skip k-blocks strictly above the diagonal.
        @pl.when(ik * block_k <= iq * block_q + block_q - 1)
        def _run():
            body()

    else:
        body()

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_ref[:, 0:1]
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked q rows → 0 output
        o_ref[0, 0] = (acc_ref[:] / l).astype(o_ref.dtype)


def _pad_to(x: jax.Array, axis: int, multiple: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _flash_forward(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool,
    block_q: int,
    block_k: int,
    interpret: bool,
) -> jax.Array:
    b, s, h, d = q.shape
    scale = d**-0.5

    # [b, s, h, d] → [b, h, s, d]; pad head dim to the 128-lane width and
    # the sequence to a block multiple. Zero-padded head lanes add 0 to
    # q·k and produce zero output columns (sliced off below).
    seq_multiple = math.lcm(block_q, block_k)

    def prep(x):
        x = jnp.moveaxis(x, 1, 2)
        x = _pad_to(x, 3, _LANES)
        return _pad_to(x, 2, seq_multiple)

    qp, kp, vp = prep(q), prep(k), prep(v)
    s_pad, d_pad = qp.shape[2], qp.shape[3]
    block_q = min(block_q, s_pad)
    block_k = min(block_k, s_pad)
    assert s_pad % block_q == 0 and s_pad % block_k == 0
    nq, nk = s_pad // block_q, s_pad // block_k

    kernel = functools.partial(
        _flash_fwd_kernel,
        scale=scale,
        causal=causal,
        block_q=block_q,
        block_k=block_k,
        s_valid=s,
    )
    out = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec(
                (1, 1, block_q, d_pad), lambda ib, ih, iq, ik: (ib, ih, iq, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_k, d_pad), lambda ib, ih, iq, ik: (ib, ih, ik, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_k, d_pad), lambda ib, ih, iq, ik: (ib, ih, ik, 0)
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, d_pad), lambda ib, ih, iq, ik: (ib, ih, iq, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, s_pad, d_pad), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d_pad), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
        interpret=interpret,
    )(qp, kp, vp)
    return jnp.moveaxis(out[:, :, :s, :d], 2, 1)  # → [b, s, h, d]


def _attention_reference(q, k, v, causal):
    """Plain-XLA attention used for the recomputing backward pass."""
    b, s, h, d = q.shape
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * (d**-0.5)
    if causal:
        mask = jnp.tril(jnp.ones((s, s), dtype=bool))
        scores = jnp.where(mask[None, None], scores, _NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_attention(q, k, v, causal, block_q, block_k, interpret):
    return _flash_forward(q, k, v, causal, block_q, block_k, interpret)


def _flash_fwd(q, k, v, causal, block_q, block_k, interpret):
    out = _flash_forward(q, k, v, causal, block_q, block_k, interpret)
    return out, (q, k, v)


def _flash_bwd(causal, block_q, block_k, interpret, residuals, g):
    q, k, v = residuals
    _, vjp = jax.vjp(
        lambda q, k, v: _attention_reference(q, k, v, causal), q, k, v
    )
    return vjp(g)


_flash_attention.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Flash attention over ``[batch, seq, heads, head_dim]`` inputs.

    Compiles the kernel through Mosaic, which only a TPU backend can do.
    The Pallas interpreter runs only on an explicit ``interpret=True``
    (CPU tests); nothing here looks at the backend, so a run that was
    meant for the chip can never quietly take the interpreter.
    """
    return _flash_attention(q, k, v, causal, block_q, block_k, interpret)
