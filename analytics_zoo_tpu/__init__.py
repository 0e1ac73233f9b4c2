"""analytics_zoo_tpu — a TPU-native rebuild of Analytics Zoo.

One Python runtime on JAX/XLA replaces the reference's Python+JVM two-language
stack (SURVEY.md §1): estimators jit-compile user models and train data-parallel
via psum over ICI/DCN; XShards partitions live host-local and stream into HBM;
serving runs compiled executables; AutoML trials schedule onto chip subsets.
"""

__version__ = "0.1.0"

from .common.config import OrcaConfig, OrcaContext
from .common.context import (ClusterContext, get_context, init_orca_context,
                             stop_orca_context)

__all__ = [
    "OrcaConfig", "OrcaContext", "ClusterContext",
    "init_orca_context", "stop_orca_context", "get_context",
    "__version__",
]
