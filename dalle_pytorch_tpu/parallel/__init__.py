from .context import activate_mesh, active_mesh
from .loop import Dispatch, TrainLoop
from .mesh import AXIS_NAMES, MeshRuntime, init_distributed, make_runtime
from .pipeline import gpipe, stack_layer_params
from .sharding import (
    DEFAULT_RULES,
    opt_state_shardings,
    params_shardings,
    partition_spec,
    shard_pytree,
)
from .step import TrainState, create_train_state, make_eval_step, make_train_step

__all__ = [
    "AXIS_NAMES",
    "DEFAULT_RULES",
    "Dispatch",
    "MeshRuntime",
    "TrainLoop",
    "TrainState",
    "activate_mesh",
    "active_mesh",
    "create_train_state",
    "gpipe",
    "init_distributed",
    "make_eval_step",
    "make_runtime",
    "make_train_step",
    "opt_state_shardings",
    "params_shardings",
    "partition_spec",
    "shard_pytree",
    "stack_layer_params",
]
