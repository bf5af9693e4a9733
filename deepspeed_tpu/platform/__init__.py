from .accelerator import (TpuAccelerator, get_accelerator, init_distributed,
                          set_accelerator)
from .compile_cache import configure_compile_cache
from .mesh import (AXIS_ORDER, BATCH_AXES, MeshSpec, batch_pspec, build_mesh,
                   dp_world_size, named_sharding, replicated)

__all__ = ["configure_compile_cache", "TpuAccelerator", "get_accelerator", "set_accelerator", "init_distributed",
           "MeshSpec", "build_mesh", "AXIS_ORDER", "BATCH_AXES", "batch_pspec",
           "dp_world_size", "named_sharding", "replicated"]
