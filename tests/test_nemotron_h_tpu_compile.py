"""The Mamba-2 mixer of ``tpusnap.models.nemotron_h`` at the cell's real
shapes (1 x 8192, 8 heads of 64 channels in 1 group, a state of 128, chunks
of 128; d 2688), forward and backward, compiled for a described TPU v5e
without the chip: the compiler takes the chunked scan as ``jax.numpy`` wrote
it (no Pallas, so no kernel to refuse), the chain between chunks is the
mixer's one loop each way and nothing else in it is sequential, and a
recomputed mixer keeps well under half a gigabyte alive (the float32 decay
masks of 64 chunks x 8 heads x 128 x 128 are 34 MB each). Nothing runs: a
compile that passes is not a chip run. The topology is described inside a
fixture of this file alone (one process at a time may load the TPU's
library); where it cannot be described the tests skip."""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from tpusnap.models.nemotron_h import NemotronH, NemotronHConfig

CFG = NemotronHConfig()
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


def test_the_mixer_compiles_for_the_chip_with_one_short_chain(one_chip):
    model = NemotronH(CFG)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))["layers"]["01"]
    lp = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), shapes)
    u = jax.ShapeDtypeStruct((1, SEQ, CFG.d_model), jnp.float32, sharding=one_chip)

    def loss(lp, u):
        return jnp.sum(jax.checkpoint(model.mamba)(lp, u) ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(lp, u).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" not in text  # plain jax.numpy: no kernel of its own
    # The chain over the 64 chunks, forward, recomputed and backward: the
    # mixer's only loops, and each is the scan's.
    loops = [ln for ln in text.splitlines() if " while(" in ln]
    assert 1 <= len(loops) <= 3, len(loops)
    assert all("ssm.scan" in ln and '/while"' in ln for ln in loops), loops
    # One mixer's recompute and backward: the masks, not the sequence squared
    # (178 MB by this compiler's count).
    assert compiled.memory_analysis().temp_size_in_bytes < 2**29
