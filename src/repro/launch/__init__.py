# Launch layer: production mesh and sharding rules.
from .mesh import axis_size, dp_axes, make_production_mesh, make_smoke_mesh
from .sharding import (
    batch_shardings,
    cache_shardings,
    opt_shardings,
    param_spec,
    params_shardings,
)

__all__ = [
    "make_production_mesh",
    "make_smoke_mesh",
    "dp_axes",
    "axis_size",
    "param_spec",
    "params_shardings",
    "opt_shardings",
    "batch_shardings",
    "cache_shardings",
]
