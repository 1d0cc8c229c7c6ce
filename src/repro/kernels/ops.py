"""Jit'd public API over the Pallas stencil kernels.

``stencil_run(name, x, steps)`` executes T time steps of the named stencil;
block sizes default to the codesign-planned VMEM tiling
(:func:`repro.kernels.stencil_common.plan_block_rows`) and can be overridden
with explicitly optimized values (what `repro.core`'s software-parameter
solve produces).
"""

from __future__ import annotations

import functools
from types import ModuleType
from typing import Dict

import jax

from . import gradient2d, heat2d, heat3d, jacobi2d, laplacian2d, laplacian3d
from .stencil_common import plan_block_rows, time_loop

__all__ = ["KERNELS", "stencil_step", "stencil_run", "kernel_flops", "tuned_block_rows"]

KERNELS: Dict[str, ModuleType] = {
    m.NAME: m
    for m in (jacobi2d, heat2d, laplacian2d, gradient2d, heat3d, laplacian3d)
}


def kernel_flops(name: str, shape, steps: int = 1) -> float:
    """Useful flops of a run (interior points only -- borders are copies)."""
    mod = KERNELS[name]
    interior = 1.0
    for d in shape:
        interior *= max(d - 2 * mod.HALO, 0)
    return mod.FLOPS_PER_POINT * interior * steps


def tuned_block_rows(name: str, shape, dtype) -> int:
    """The default software parameter: the eq.-(9)/(11) VMEM-fit solve."""
    del name  # all current kernels have halo 1 and 4 resident bands
    return plan_block_rows(shape, dtype)


def stencil_step(name: str, x: jax.Array, block_rows=None, *, interpret: bool):
    """One un-jitted stencil application. ``interpret=True`` runs the
    Pallas interpreter (any backend); ``False`` compiles for the TPU."""
    return KERNELS[name].step(x, block_rows=block_rows, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("name", "steps", "block_rows", "interpret"))
def stencil_run(
    name: str,
    x: jax.Array,
    steps: int = 1,
    block_rows: int | None = None,
    *,
    interpret: bool,
) -> jax.Array:
    """T time steps of the named stencil (Dirichlet borders)."""
    mod = KERNELS[name]
    step = functools.partial(mod.step, block_rows=block_rows, interpret=interpret)
    return time_loop(step, x, steps)
