from .mesh import (batch_divisor, create_mesh, data_sharding,
                   mesh_axis_size, parse_mesh_axes, replicated,
                   resolve_axis_sizes)
from .sharding import BucketLayout, FsdpPlan, SpecLayout
from .expert_parallel import (expert_sharding, moe_apply,
                              stack_expert_params)
from .pipeline_parallel import (pipeline_apply, stack_stage_params,
                                stage_sharding)
from .tensor_parallel import (TPDense, TPMLP, TPSelfAttention,
                              TPTransformerBlock)

__all__ = ["create_mesh", "data_sharding", "replicated", "resolve_axis_sizes",
           "mesh_axis_size", "batch_divisor", "parse_mesh_axes",
           "BucketLayout",
           "SpecLayout", "FsdpPlan",
           "TPDense", "TPMLP", "TPSelfAttention", "TPTransformerBlock",
           "pipeline_apply", "stack_stage_params", "stage_sharding",
           "moe_apply", "stack_expert_params", "expert_sharding"]
