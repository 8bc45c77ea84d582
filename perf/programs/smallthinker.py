"""The program ``smallthinker``: one chip's share of a sparse-expert
decoder with window and global attention, on tpusnap's normal path.

``tpusnap.models.SmallThinker`` under the same ``make_train_step`` and
``init_train_state`` as the flagship transformer, on the mesh the
configuration states: float32 parameters and Adam moments made on the
device from the seed, one subtree a layer, bf16 compute, a step that
donates nothing. The configuration's keys are the source's own; the
experts, heads and vocabulary rows it counts are those held here.
"""

from __future__ import annotations

from typing import Any, Dict

# Imported with the module, not inside ``build``: on a tree whose
# ``tpusnap.models`` lacks the model, the run ends when the harness looks
# the program up, before the plain reference's minutes.
from tpusnap.models import SmallThinker, SmallThinkerConfig, make_mesh, make_train_step
from tpusnap.models.transformer import init_train_state, token_sharding, train_state_shardings


def build(config: Dict[str, Any], devices, key) -> Dict[str, Any]:
    layers = int(config["num_hidden_layers"])
    cfg = SmallThinkerConfig(
        vocab_size=int(config["vocab_size"]),
        d_model=int(config["hidden_size"]),
        n_heads=int(config["num_attention_heads"]),
        n_kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config["head_dim"]),
        n_layers=layers,
        d_expert=int(config["moe_ffn_hidden_size"]),
        n_experts=int(config["moe_router_outputs"]),
        top_k=int(config["moe_num_active_primary_experts"]),
        first_expert=int(config["moe_first_expert"]),
        n_held_experts=int(config["moe_num_primary_experts"]),
        window=int(config["sliding_window_size"]),
        rope_theta=float(config["rope_theta"]),
        rope_layout=tuple(int(x) for x in config["rope_layout"][:layers]),
        window_layout=tuple(int(x) for x in config["sliding_window_layout"][:layers]),
    )
    model = SmallThinker(cfg)
    mesh = make_mesh(devices, tuple(config["mesh"]))
    return {
        "mesh": mesh,
        "state": init_train_state(model, mesh, key),
        "train_step": make_train_step(model, mesh),
        "state_shardings": train_state_shardings(model, mesh),
        "token_sharding": token_sharding(cfg, mesh),
    }
