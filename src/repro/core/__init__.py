"""Core library: the paper's contribution -- analytical area/time models and
the non-linear codesign optimizer (plus the TPU re-instantiation used by the
LM framework's mesh/sharding autotuner)."""

from .area import (  # noqa: F401
    GTX980,
    MAXWELL,
    TITAN_X,
    HardwarePoint,
    LinearAreaModel,
    cacheless,
)
from .codesign import (  # noqa: F401
    CodesignResult,
    HardwareSpace,
    codesign,
    enumerate_hw_space,
    evaluate_fixed_hw,
)
from .pareto import pareto_front, pareto_mask  # noqa: F401
from .solver import LATTICE_2D, LATTICE_3D, TileLattice, refine_point, solve_cell  # noqa: F401

# .sweep imports jax at module scope (~1s); load it lazily (PEP 562) so the
# pure-NumPy oracle/area paths keep the seed's cheap `import repro.core`.
_SWEEP_EXPORTS = (
    "device_count",
    "refine_points",
    "sweep_cell",
    "sweep_cells",
    "sweep_cells_sharded",
)


def __getattr__(name):
    if name in _SWEEP_EXPORTS:
        from . import sweep

        return getattr(sweep, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
from .timemodel import (  # noqa: F401
    MAXWELL_GPU,
    STENCILS,
    TITANX_GPU,
    GPUSpec,
    ProblemSize,
    StencilSpec,
    stencil_gflops,
    stencil_time,
)
from .workload import Workload, WorkloadCell, paper_sizes, paper_workload  # noqa: F401
