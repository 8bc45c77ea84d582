"""``tpusnap.ops.ssd_scan`` against the recurrence it stands for, written
out position by position here (a ``lax.scan`` over the sequence, float32):
value and every gradient, at lengths that are and are not multiples of the
chunk, with several heads to a group, at decays near 0 and near 1, and with
bf16 operands. CPU, seeded inputs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from tpusnap.ops import ssd_scan

# The same mathematics in another order of float32 sums (products of a chunk
# against a chain of positions): a few units in the last place, times the
# positions a state has been summed over.
F32 = 2e-5
# bf16 operands, float32 accumulation: 2^-8 an operand, and the products of a
# chunk multiply three rounded operands (the decayed scores, dt x, C or B).
BF16 = 3e-2


def recurrence(x, dt, a, b, c):
    """``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t``."""
    batch, _, heads, channels = x.shape
    per = heads // b.shape[2]
    b, c = jnp.repeat(b, per, axis=2), jnp.repeat(c, per, axis=2)

    def step(state, at):
        x_t, dt_t, b_t, c_t = at
        state = (jnp.exp(dt_t * a)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :])
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t)

    start = jnp.zeros((batch, heads, channels, b.shape[-1]), jnp.float32)
    _, y = lax.scan(step, start, tuple(t.swapaxes(0, 1) for t in (x, dt, b, c)))
    return y.swapaxes(0, 1)


def inputs(seq, heads=4, groups=2, channels=4, state=8, batch=2, seed=0, decay=(-3.0, 2.0)):
    """Seeded inputs; ``decay`` is the range of ``log(-A)``, so that with
    ``dt`` about 0.7 a position's decay ``exp(dt A)`` runs from all but 1
    (``A`` near 0) to all but 0 (``A`` in the tens)."""
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(k[0], (batch, seq, heads, channels))
    dt = jax.nn.softplus(jax.random.normal(k[1], (batch, seq, heads)))
    a = -jnp.exp(jax.random.uniform(k[2], (heads,), minval=decay[0], maxval=decay[1]))
    b = jax.random.normal(k[3], (batch, seq, groups, state))
    c = jax.random.normal(k[4], (batch, seq, groups, state))
    return x, dt, a, b, c


def gap(got, want):
    return float(jnp.linalg.norm(got - want)) / float(jnp.linalg.norm(want))


@pytest.fixture(scope="module", autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.mark.parametrize("seq,chunk", [(32, 8), (37, 8), (5, 8), (128, 128), (130, 64)],
                         ids=["whole_chunks", "a_ragged_last_chunk", "shorter_than_a_chunk",
                              "one_chunk", "the_cells_chunking_ragged"])
def test_the_chunked_scan_is_the_recurrence(seq, chunk):
    args = inputs(seq)
    got = jax.jit(lambda *t: ssd_scan(*t, chunk=chunk, dtype=jnp.float32))(*args)
    want = jax.jit(recurrence)(*args)
    assert got.shape == want.shape == (2, seq, 4, 4) and got.dtype == jnp.float32
    assert gap(got, want) <= F32


@pytest.mark.parametrize("seq,chunk", [(37, 8), (64, 16)], ids=["ragged", "whole"])
def test_every_gradient_of_the_chunked_scan_is_the_recurrences(seq, chunk):
    """``x``, ``dt``, ``A``, ``B`` and ``C``: differentiated by JAX through
    the masks, the running sums and the chain over chunks; the padded
    positions of a ragged last chunk hand nothing back."""
    args = inputs(seq, seed=1)

    def through(scan):
        return jax.jit(jax.grad(lambda *t: jnp.sum(jnp.sin(scan(*t))), argnums=(0, 1, 2, 3, 4)))

    got = through(lambda *t: ssd_scan(*t, chunk=chunk, dtype=jnp.float32))(*args)
    want = through(recurrence)(*args)
    for name, g, w in zip(("x", "dt", "a", "b", "c"), got, want):
        assert g.shape == w.shape and bool(jnp.isfinite(g).all()), name
        assert gap(g, w) <= 10 * F32, (name, gap(g, w))


@pytest.mark.parametrize("log_a", [-12.0, -6.0, 3.0, 6.0],
                         ids=["decay_all_but_1", "decay_near_1", "decay_near_0",
                              "decay_underflows"])
def test_decays_near_0_and_near_1(log_a):
    """``A = -exp(log_a)`` for every head. Near 1 the state is a plain
    running sum over the whole sequence, and the chain between chunks
    carries all of it; near 0 (at ``log_a`` 6 ``exp(dt A)`` is e^-20 and
    less and the running sum inside a chunk thousands below zero, which a
    ratio of two ``exp``s would turn into 0 / 0) a position sees itself
    alone: ``y_t = dt_t (C_t . B_t) x_t``. Value and gradients stay finite
    and are the recurrence's."""
    x, dt, _, b, c = inputs(48, heads=2, groups=1, seed=2)
    a = -jnp.exp(jnp.full((2,), log_a))
    scan = lambda *t: ssd_scan(*t, chunk=16, dtype=jnp.float32)  # noqa: E731
    got, want = jax.jit(scan)(x, dt, a, b, c), jax.jit(recurrence)(x, dt, a, b, c)
    assert bool(jnp.isfinite(got).all()) and gap(got, want) <= F32
    if log_a == 6.0:
        alone = dt[..., None] * jnp.einsum("bsgn,bsgn->bsg", c, b)[..., None] * x
        assert gap(got, alone) <= 1e-5
    if log_a == -12.0:
        # No decay to speak of: the state at t is the sum of dt x B^T over all s <= t.
        summed = jnp.cumsum(jnp.einsum("bsh,bshp,bsn->bshpn", dt, x, b[:, :, 0]), axis=1)
        assert gap(got, jnp.einsum("bshpn,bsn->bshp", summed, c[:, :, 0])) <= 1e-3
    grads = jax.jit(jax.grad(lambda *t: jnp.sum(scan(*t) ** 2), argnums=(0, 1, 2, 3, 4)))(
        x, dt, a, b, c)
    wants = jax.jit(jax.grad(lambda *t: jnp.sum(recurrence(*t) ** 2), argnums=(0, 1, 2, 3, 4)))(
        x, dt, a, b, c)
    for name, g, w in zip(("x", "dt", "a", "b", "c"), grads, wants):
        assert bool(jnp.isfinite(g).all()), name
        # ``A``'s gradient is one sum over every position of terms of both
        # signs that all but vanish where the decay is near 0 (5e-10 at
        # ``log_a`` 6, beside gradients of order 1): float32 cancels it to a
        # thousandth on either side, and below 1e-7 it is rounding alone.
        rel, floor = (2e-3, 1e-7) if name == "a" else (10 * F32, 1e-30)
        assert float(jnp.linalg.norm(g - w)) <= rel * float(jnp.linalg.norm(w)) + floor, name


def test_heads_of_a_group_share_its_b_and_c_and_groups_do_not_mix():
    """Head ``h`` reads group ``h // (heads / groups)``: a scan over 4 heads
    in 2 groups is two scans over 2 heads in 1 group, side by side."""
    x, dt, a, b, c = inputs(24, heads=4, groups=2, seed=3)
    whole = ssd_scan(x, dt, a, b, c, chunk=8, dtype=jnp.float32)
    for g in range(2):
        heads = slice(2 * g, 2 * g + 2)
        part = ssd_scan(x[:, :, heads], dt[:, :, heads], a[heads], b[:, :, g:g + 1],
                        c[:, :, g:g + 1], chunk=8, dtype=jnp.float32)
        np.testing.assert_allclose(whole[:, :, heads], part, rtol=1e-5, atol=1e-6)
    with pytest.raises(ValueError, match="multiple of groups"):
        ssd_scan(x[:, :, :3], dt[:, :, :3], a[:3], b, c, chunk=8)


def test_position_t_reads_nothing_after_t():
    x, dt, a, b, c = inputs(40, seed=4)
    base = ssd_scan(x, dt, a, b, c, chunk=8, dtype=jnp.float32)
    t = 19  # inside the third chunk
    moved = ssd_scan(x.at[:, t + 1:].add(1.0), dt.at[:, t + 1:].mul(2.0), a,
                     b.at[:, t + 1:].add(1.0), c.at[:, t + 1:].add(1.0),
                     chunk=8, dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(base[:, :t + 1]), np.asarray(moved[:, :t + 1]))
    assert float(jnp.abs(base[:, t + 1:] - moved[:, t + 1:]).max()) > 0.1


def test_bf16_operands_read_the_recurrence_to_their_rounding():
    """The chip's arithmetic: bf16 operands in the three products of a
    chunk, float32 sums, decays and state between chunks."""
    args = inputs(130, heads=8, groups=1, channels=16, state=32, seed=5, decay=(-4.0, 0.0))
    got = jax.jit(lambda *t: ssd_scan(*t, chunk=32))(*args)
    want = jax.jit(recurrence)(*args)
    assert got.dtype == jnp.float32 and F32 < gap(got, want) <= BF16
