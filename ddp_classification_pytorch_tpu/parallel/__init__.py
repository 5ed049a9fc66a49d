from .mesh import (
    MeshSpec, make_mesh, batch_sharding, replicated, make_global_array,
    param_shardings,
)
from .collectives import build_ddp_model

__all__ = [
    "MeshSpec", "make_mesh", "batch_sharding", "replicated",
    "make_global_array", "param_shardings",
    "build_ddp_model",
]
