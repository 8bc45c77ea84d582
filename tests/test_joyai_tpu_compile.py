"""The latent attention of ``tpusnap.models.joyai`` at the cell's real shapes
(1 x 8192, 4 heads of 128 + 64 and 128, a latent of 512), forward and
backward, compiled for a described TPU v5e without the chip: the compiler
takes it, and the softmax's row maximum is a reduction and no reduce-window
over the whole row (which it is for scores laid out ``[batch, heads, queries,
keys]``: 35 ms a query block on the chip, PERF.md 6, PR 46). Nothing runs:
a compile that passes is not a chip run. The topology is described inside a
fixture of this file alone (one process at a time may load the TPU's
library); where it cannot be described the tests skip."""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from tpusnap.models.joyai import JoyAIConfig, latent_attention

CFG = JoyAIConfig()
SEQ = 8192


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here, or its library is held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_latent_attention_compiles_for_the_chip_without_a_reduce_window(one_chip):
    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.bfloat16, sharding=one_chip)

    def loss(q, c, k_rope, w_kvb):
        out = latent_attention(q, c, k_rope, w_kvb, d_nope=CFG.d_nope, q_block=CFG.q_block)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(
        shape(1, SEQ, CFG.n_heads, CFG.d_nope + CFG.d_rope), shape(1, SEQ, CFG.kv_rank),
        shape(1, SEQ, CFG.d_rope), shape(CFG.kv_rank, CFG.n_heads * (CFG.d_nope + CFG.d_v)),
    ).compile()
    text = compiled.as_text()
    assert "reduce-window(" not in text
    # One query block's float32 scores and what its backward keeps, not the sequence's.
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 2**30
