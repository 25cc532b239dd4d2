"""Neural-net layer library (functional, pytree-parameterized)."""

from nezha_tpu.nn.module import (
    Module,
    Sequential,
    Variables,
    make_variables,
    child_vars,
    child_rng,
    run_child,
)
from nezha_tpu.nn.layers import (
    Linear,
    Conv2d,
    BatchNorm,
    LayerNorm,
    RMSNorm,
    Embedding,
    Dropout,
    max_pool,
    avg_pool,
    global_avg_pool,
)
from nezha_tpu.nn import initializers

__all__ = [
    "Module", "Sequential", "Variables", "make_variables", "child_vars",
    "child_rng", "run_child", "Linear", "Conv2d", "BatchNorm", "LayerNorm", "RMSNorm",
    "Embedding",
    "Dropout", "max_pool", "avg_pool", "global_avg_pool", "initializers",
]
