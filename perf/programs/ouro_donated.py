"""The program ``ouro_donated``: a looped decoder with an exit gate on
tpusnap's normal path, as a job runs it when the model fills the chip.

``tpusnap.models.Ouro`` under the same ``make_train_step`` and
``init_train_state`` as the flagship transformer, on the mesh the
configuration states: float32 parameters and Adam moments made on the
device from the seed, the layers stacked, bf16 compute. The step is what
``make_train_step`` returns, compiled once more with ``donate_argnums=0``,
so that the state a step is handed is deleted and its buffers are the new
state's: 16 bytes a parameter are live under a pending take. Returns
``"donates": True``: see ``perf/README.md``, "What a program builds".
"""

from __future__ import annotations

from typing import Any, Dict

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

# Imported with the module, not inside ``build``: on a tree whose
# ``tpusnap.models`` lacks the model, the run ends when the harness looks
# the program up, before the plain reference's minutes.
from tpusnap.models import Ouro, OuroConfig, make_mesh, make_train_step
from tpusnap.models.transformer import init_train_state, token_sharding, train_state_shardings


def build(config: Dict[str, Any], devices, key) -> Dict[str, Any]:
    if int(config["num_key_value_heads"]) != int(config["num_attention_heads"]):
        raise ValueError("the model groups no heads: as many KV heads as heads")
    if float(config["rms_norm_eps"]) != 1e-6:
        raise ValueError("the model's norms add 1e-6 under the root")
    cfg = OuroConfig(
        vocab_size=int(config["vocab_size"]),
        d_model=int(config["hidden_size"]),
        n_heads=int(config["num_attention_heads"]),
        head_dim=int(config["head_dim"]),
        n_layers=int(config["num_hidden_layers"]),
        d_ff=int(config["intermediate_size"]),
        n_passes=int(config["total_ut_steps"]),
        rope_theta=float(config["rope_theta"]),
        entropy_weight=float(config["assumed"]["beta"]),
    )
    model = Ouro(cfg)
    mesh = make_mesh(devices, tuple(config["mesh"]))
    state_shardings = train_state_shardings(model, mesh)
    tokens = token_sharding(cfg, mesh)
    return {
        "mesh": mesh,
        "state": init_train_state(model, mesh, key),
        "train_step": jax.jit(
            make_train_step(model, mesh),
            donate_argnums=0,
            in_shardings=(state_shardings, tokens),
            out_shardings=(state_shardings, NamedSharding(mesh, P())),
        ),
        "state_shardings": state_shardings,
        "token_sharding": tokens,
        "donates": True,
    }
