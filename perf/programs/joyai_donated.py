"""The program ``joyai_donated``: one chip's share of a latent-attention,
sparse-expert decoder with a multi-token-prediction module on tpusnap's
normal path, as a job runs it when the model fills the chip.

``tpusnap.models.JoyAI`` under the same ``make_train_step`` and
``init_train_state`` as the flagship transformer, on the mesh the
configuration states: float32 parameters and Adam moments made on the
device from the seed, one subtree a layer, bf16 compute. The configuration's
keys are the source's own; the layers, experts, heads and vocabulary rows
it counts are those held here. The step is what ``make_train_step``
returns, compiled once more with ``donate_argnums=0``, so that the state a
step is handed is deleted and its buffers are the new state's: 16 bytes a
parameter are live under a pending take. Returns ``"donates": True``: see
``perf/README.md``, "What a program builds".
"""

from __future__ import annotations

from typing import Any, Dict

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

# Imported with the module, not inside ``build``: on a tree whose
# ``tpusnap.models`` lacks the model, the run ends when the harness looks
# the program up, before the plain reference's minutes.
from tpusnap.models import JoyAI, JoyAIConfig, make_mesh, make_train_step
from tpusnap.models.transformer import init_train_state, token_sharding, train_state_shardings


def build(config: Dict[str, Any], devices, key) -> Dict[str, Any]:
    if int(config["num_key_value_heads"]) != int(config["num_attention_heads"]):
        raise ValueError("latent attention groups no heads: as many KV heads as heads")
    if float(config["rms_norm_eps"]) != 1e-6:
        raise ValueError("the model's norms add 1e-6 under the root")
    if int(config["n_shared_experts"]) != 1 or int(config["moe_layer_freq"]) != 1:
        raise ValueError("the model has one shared expert, and an expert layer after every dense one")
    if int(config["n_group"]) != 1 or int(config["topk_group"]) != 1:
        raise ValueError("the model's router has no group limit")
    cfg = JoyAIConfig(
        vocab_size=int(config["vocab_size"]),
        d_model=int(config["hidden_size"]),
        n_heads=int(config["num_attention_heads"]),
        q_rank=int(config["q_lora_rank"]),
        kv_rank=int(config["kv_lora_rank"]),
        d_nope=int(config["qk_nope_head_dim"]),
        d_rope=int(config["qk_rope_head_dim"]),
        d_v=int(config["v_head_dim"]),
        n_layers=int(config["num_hidden_layers"]),
        n_dense_layers=int(config["first_k_dense_replace"]),
        d_ff=int(config["intermediate_size"]),
        d_expert=int(config["moe_intermediate_size"]),
        n_experts=int(config["moe_router_outputs"]),
        top_k=int(config["num_experts_per_tok"]),
        first_expert=int(config["moe_first_expert"]),
        n_held_experts=int(config["n_routed_experts"]),
        routed_scale=float(config["routed_scaling_factor"]),
        n_mtp=int(config["num_nextn_predict_layers"]),
        mtp_weight=float(config["assumed"]["mtp_lambda"]),
        rope_theta=float(config["rope_theta"]),
    )
    model = JoyAI(cfg)
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
