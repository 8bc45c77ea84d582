"""Flagship models: TPU-first reference workloads for tpusnap.

The reference library ships example *training scripts* (DDP / FSDP /
torchrec DLRM, SURVEY.md §2 #23-24) but no model code of its own. tpusnap
ships six model families: a flagship decoder transformer whose parameter
pytree exercises every sharding family the checkpoint preparers must
handle — DP (replicated), FSDP (param-sharded), TP (tensor-parallel),
SP/CP (ring attention over a sequence axis) and EP (expert-sharded MoE
weights) —, a sharded embedding-table collection (the torchrec DMP
analog: row/col/table-wise layouts, host-offloaded tables, row-wise
Adagrad state), one chip's share of a sparse-expert decoder with
window and global attention (``smallthinker``: top-k token dispatch over
the experts held here, one subtree a layer, so a state of many leaves of
a few tens of MiB), a looped decoder (``ouro``: one stack of layers
run several times over the same leaves, sandwich norms, an exit gate and
a loss term at every pass, so a gradient that sums over a leaf's uses and
a state with leaves of one element beside leaves of hundreds of MB), and
one chip's share of a latent-attention, sparse-expert decoder (``joyai``:
keys and values rebuilt from a low-rank latent in every query block, a
leading dense layer, a shared expert beside a sigmoid-routed share of the
experts with a correction bias that no step changes, and a
multi-token-prediction module that reads the embedding and the head a
second time, so a state of hundreds of leaves, most of them a few MB),
and one chip's share of a hybrid state-space / sparse-expert decoder
(``nemotron_h``: one mixer a layer by a pattern; Mamba-2 mixers with a
chunked scan, told which heads and which group they hold; experts of two
matrices with a squared ReLU beside a shared one; grouped-query attention
with no position term; so a state with leaves of 8 elements and
three-dimensional convolution leaves beside banks of 160 MB whose minor
dimension is no tile's).
"""

from .embedding import (  # noqa: F401
    EmbeddingCollection,
    TableConfig,
    make_embedding_train_step,
)
from .joyai import JoyAI, JoyAIConfig  # noqa: F401
from .nemotron_h import NemotronH, NemotronHConfig  # noqa: F401
from .ouro import Ouro, OuroConfig  # noqa: F401
from .smallthinker import SmallThinker, SmallThinkerConfig  # noqa: F401
from .transformer import (  # noqa: F401
    Transformer,
    TransformerConfig,
    make_mesh,
    make_train_step,
)

__all__ = [
    "EmbeddingCollection",
    "JoyAI",
    "JoyAIConfig",
    "NemotronH",
    "NemotronHConfig",
    "Ouro",
    "OuroConfig",
    "SmallThinker",
    "SmallThinkerConfig",
    "TableConfig",
    "Transformer",
    "TransformerConfig",
    "make_embedding_train_step",
    "make_mesh",
    "make_train_step",
]
