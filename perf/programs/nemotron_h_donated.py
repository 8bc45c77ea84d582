"""The program ``nemotron_h_donated``: one chip's share of a hybrid
state-space / sparse-expert decoder (Mamba-2 mixers, expert feed-forwards
of squared-ReLU experts beside a shared one, grouped-query attention with no
position term; one mixer a layer) on tpusnap's normal path, as a job runs it
when the model fills the chip.

``tpusnap.models.NemotronH`` under the same ``make_train_step`` and
``init_train_state`` as the flagship transformer, on the mesh the
configuration states: float32 parameters and Adam moments made on the
device from the seed, one subtree a layer, bf16 compute. The configuration's
keys are the source's own; the layers, Mamba-2 heads and groups, experts,
attention heads and vocabulary rows it counts are those held here. The step
is what ``make_train_step`` returns, compiled once more with
``donate_argnums=0``, so that the state a step is handed is deleted and its
buffers are the new state's: 16 bytes a parameter are live under a pending
take. Returns ``"donates": True``: see ``perf/README.md``, "What a program
builds".
"""

from __future__ import annotations

from typing import Any, Dict

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

# Imported with the module, not inside ``build``: on a tree whose
# ``tpusnap.models`` lacks the model, the run ends when the harness looks
# the program up, before the plain reference's minutes.
from tpusnap.models import NemotronH, NemotronHConfig, make_mesh, make_train_step
from tpusnap.models.transformer import init_train_state, token_sharding, train_state_shardings


def build(config: Dict[str, Any], devices, key) -> Dict[str, Any]:
    if len(config["hybrid_override_pattern"]) != int(config["num_hidden_layers"]):
        raise ValueError("the pattern names one mixer for each of the layers held")
    if int(config["n_shared_experts"]) != 1:
        raise ValueError("the model has one shared expert")
    if int(config["n_group"]) != 1 or int(config["topk_group"]) != 1:
        raise ValueError("the model's router has no group limit")
    if (config["mlp_hidden_act"], config["mamba_hidden_act"]) != ("relu2", "silu"):
        raise ValueError("the model's experts are relu^2 and its mixer's gates silu")
    if any(config[k] for k in ("attention_bias", "mamba_proj_bias", "mlp_bias", "use_bias")) or (
            not config["use_conv_bias"]):
        raise ValueError("the model has no bias on any matrix and one on the convolution")
    if float(config["norm_eps"]) != float(config["layer_norm_epsilon"]):
        raise ValueError("the model has one epsilon for all its norms")
    cfg = NemotronHConfig(
        vocab_size=int(config["vocab_size"]),
        d_model=int(config["hidden_size"]),
        pattern=str(config["hybrid_override_pattern"]),
        ssm_heads=int(config["mamba_num_heads"]),
        ssm_head_dim=int(config["mamba_head_dim"]),
        ssm_groups=int(config["n_groups"]),
        ssm_state=int(config["ssm_state_size"]),
        conv_kernel=int(config["conv_kernel"]),
        chunk=int(config["chunk_size"]),
        dt_min=float(config["time_step_min"]),
        dt_max=float(config["time_step_max"]),
        dt_floor=float(config["time_step_floor"]),
        d_expert=int(config["moe_intermediate_size"]),
        d_shared=int(config["moe_shared_expert_intermediate_size"]),
        n_experts=int(config["moe_router_outputs"]),
        top_k=int(config["num_experts_per_tok"]),
        first_expert=int(config["moe_first_expert"]),
        n_held_experts=int(config["n_routed_experts"]),
        routed_scale=float(config["routed_scaling_factor"]),
        n_heads=int(config["num_attention_heads"]),
        n_kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config["head_dim"]),
        norm_eps=float(config["layer_norm_epsilon"]),
    )
    model = NemotronH(cfg)
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
