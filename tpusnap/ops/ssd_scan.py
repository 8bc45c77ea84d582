"""The chunked scan of a state-space mixer with a scalar decay a head
(Mamba-2's "state-space duality" form) — pure ``jax.numpy``, differentiated
by JAX, no Pallas.

Per head, with a state ``S`` of ``[channels, state]``, ``S_0 = 0``:

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T        y_t = S_t C_t

``A`` is one negative scalar a head, ``dt_t > 0`` one scalar a head and
position, ``B_t`` and ``C_t`` vectors of ``state`` elements that the heads of
a group share. The recurrence is a chain of ``seq`` steps; the chunked form
is matrix products over ``chunk`` positions at a time and a chain of only
``seq / chunk`` steps between them. With ``a_t = dt_t A`` and ``L_t`` its
running sum inside a chunk (``L_t <= 0``, falling):

- the chunk's own positions give ``sum_{s<=t} exp(L_t - L_s) (C_t . B_s)
  dt_s x_s``: two products under a lower-triangular decay mask;
- the chunk's own state is ``sum_s exp(L_Q - L_s) dt_s x_s B_s^T``;
- the state entering chunk ``c`` is ``H_c = exp(L_Q of chunk c-1) H_{c-1} +
  (own state of chunk c-1)``, ``H_0 = 0`` (a ``lax.scan`` over chunks), and
  adds ``exp(L_t) H_c C_t`` at position ``t`` of chunk ``c``.

Every decay is ``exp`` of a difference of running sums that is taken first
and is never positive: no ratio of two ``exp``\\ s, so a decay near 0 (a sum of
hundreds below zero) underflows to 0 and nothing overflows. The running
sums, the decays and the state between chunks are float32; the products take
``dtype`` operands (bf16 on the chip) and accumulate in float32. A length
that is no multiple of ``chunk`` is padded with positions of ``dt = 0``, which
leave the state as it is and whose outputs are cut off.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def ssd_scan(
    x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array, c: jax.Array,
    *, chunk: int = 128, dtype=jnp.bfloat16,
) -> jax.Array:
    """``y`` of the recurrence above, ``[batch, seq, heads, channels]`` in
    float32. ``x`` is ``[batch, seq, heads, channels]``, ``dt`` ``[batch,
    seq, heads]`` (positive: after its softplus), ``a`` ``[heads]``
    (negative), ``b`` and ``c`` ``[batch, seq, groups, state]``; head ``h``
    reads group ``h // (heads // groups)``. The skip term ``D x`` is the
    caller's."""
    batch, seq, heads, channels = x.shape
    groups, state = b.shape[2], b.shape[3]
    if heads % groups:
        raise ValueError("heads must be a multiple of groups")
    per = heads // groups
    pad = -seq % chunk
    if pad:
        x, dt, b, c = (jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
                       for t in (x, dt, b, c))
    n = (seq + pad) // chunk
    dt = dt.astype(jnp.float32)
    # Rows of positions, [batch, chunks, position, group, head of the group,
    # channels], and the scalars of a head and position with the position
    # last, [batch, chunks, group, head of the group, position]: a chunk's
    # ``position x position`` masks then have the positions as their minor
    # dimensions, whatever the number of heads held.
    xd = (x.astype(jnp.float32) * dt[..., None]).reshape(batch, n, chunk, groups, per, channels)
    b = b.reshape(batch, n, chunk, groups, state).astype(dtype)
    c = c.reshape(batch, n, chunk, groups, state).astype(dtype)
    steps = (dt * a.astype(jnp.float32)).reshape(batch, n, chunk, groups, per)
    run = jnp.cumsum(jnp.moveaxis(steps, 2, -1), axis=-1)  # L_t

    def by_row(scalars):  # [b, n, g, h, position] -> [b, n, position, g, h, 1]
        return jnp.moveaxis(scalars, -1, 2)[..., None]

    # The chunk's own positions: the (C_t . B_s) of a group, weighed for each
    # of its heads by exp(L_t - L_s) where s <= t.
    scores = jnp.einsum("bntgz,bnsgz->bngts", c, b, preferred_element_type=jnp.float32)
    fall = run[..., :, None] - run[..., None, :]  # [b, n, g, h, t, s]: L_t - L_s
    seen = jnp.arange(chunk)[:, None] >= jnp.arange(chunk)[None, :]
    weights = scores[:, :, :, None] * jnp.exp(jnp.where(seen, fall, -jnp.inf))
    y = jnp.einsum("bnghts,bnsghp->bntghp", weights.astype(dtype), xd.astype(dtype),
                   preferred_element_type=jnp.float32)

    # The chunk's own state, and the chain between chunks.
    last = run[..., -1]  # [b, n, g, h]: L_Q
    to_end = jnp.exp(last[..., None] - run)
    own = jnp.einsum("bnsghp,bnsgz->bnghpz", (xd * by_row(to_end)).astype(dtype), b,
                     preferred_element_type=jnp.float32)

    def enter(carry, chunk_of):
        own_c, last_c = chunk_of
        return jnp.exp(last_c)[..., None, None] * carry + own_c, carry

    start = jnp.zeros((batch, groups, per, channels, state), jnp.float32)
    _, entering = lax.scan(enter, start, (own.swapaxes(0, 1), last.swapaxes(0, 1)))
    entering = entering.swapaxes(0, 1)  # [b, n, g, h, p, z]: H_c
    carried = jnp.einsum("bnghpz,bntgz->bntghp", entering.astype(dtype), c,
                         preferred_element_type=jnp.float32)
    y = y + by_row(jnp.exp(run)) * carried
    return y.reshape(batch, n * chunk, heads, channels)[:, :seq]
