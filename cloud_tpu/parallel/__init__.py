"""TPU parallelism layer: meshes, sharding rules, planner, collectives.

This package replaces the reference's strategy-selection mechanism (generated
``tf.distribute`` prologue text, preprocess.py:124-149) with a real library:
a :class:`MeshSpec` describes named parallelism axes over the device mesh, a
planner maps a declarative machine config to a mesh layout, and sharding
rules translate logical tensor axes to mesh axes.
"""

from cloud_tpu.parallel.mesh import (
    AXIS_DP,
    AXIS_EP,
    AXIS_FSDP,
    AXIS_PP,
    AXIS_SP,
    AXIS_TP,
    CANONICAL_AXES,
    MeshSpec,
    get_global_mesh,
    set_global_mesh,
    use_mesh,
)
from cloud_tpu.parallel.planner import (
    MeshPlan,
    ParallelismHints,
    ServeLayout,
    plan_mesh,
    plan_serve_layout,
)
from cloud_tpu.parallel.sharding import (
    ShardingRules,
    DEFAULT_RULES,
    logical_to_mesh_axes,
    named_sharding,
    shard_constraint,
)

__all__ = [
    "AXIS_DP",
    "AXIS_EP",
    "AXIS_FSDP",
    "AXIS_PP",
    "AXIS_SP",
    "AXIS_TP",
    "CANONICAL_AXES",
    "MeshSpec",
    "MeshPlan",
    "ParallelismHints",
    "ShardingRules",
    "DEFAULT_RULES",
    "get_global_mesh",
    "set_global_mesh",
    "use_mesh",
    "logical_to_mesh_axes",
    "named_sharding",
    "plan_mesh",
    "plan_serve_layout",
    "ServeLayout",
    "shard_constraint",
]
