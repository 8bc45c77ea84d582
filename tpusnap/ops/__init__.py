"""TPU-native ops: collective attention kernels for long-context models.

The reference (torchsnapshot) ships no model ops — checkpointing of
SP/CP-sharded state reduces to sharded arrays (SURVEY.md §5,
"Long-context/sequence parallelism"). tpusnap ships the ops anyway so its
flagship model exercises every sharding the preparers must round-trip:
ring attention gives sequence/context parallelism over a mesh axis. And
the chunked scan of a state-space mixer with a scalar decay a head
(``ssd_scan``: matrix products inside a chunk, a short chain between
chunks), in plain ``jax.numpy``, for ``tpusnap.models.NemotronH``.
"""

from .flash_attention import flash_attention  # noqa: F401
from .ring_attention import ring_attention  # noqa: F401
from .ssd_scan import ssd_scan  # noqa: F401

__all__ = ["flash_attention", "ring_attention", "ssd_scan"]
