"""The program ``transformer_donated``: the flagship trainer as a job runs
it when the model fills the chip.

The same model, mesh, state and shardings as the program ``transformer``,
and the same step: what ``make_train_step`` returns, compiled once more
with ``donate_argnums=0``, so that the state a step is handed is deleted
and its buffers are the new state's. 16 bytes a parameter are then live
under a pending take (the loop's state and the gradients) where
``transformer`` holds 40. Returns ``"donates": True``: see
``perf/README.md``, "What a program builds".
"""

from __future__ import annotations

from typing import Any, Dict


def build(config: Dict[str, Any], devices, key) -> Dict[str, Any]:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from perf.programs import transformer

    built = transformer.build(config, devices, key)
    built["train_step"] = jax.jit(
        built["train_step"],
        donate_argnums=0,
        in_shardings=(built["state_shardings"], built["token_sharding"]),
        out_shardings=(built["state_shardings"], NamedSharding(built["mesh"], P())),
    )
    built["donates"] = True
    return built
