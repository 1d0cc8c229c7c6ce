"""Analytical execution-time model for tiled stencils (reconstruction of
Prajapati et al., PPoPP 2017 [27] -- see DESIGN.md §3).

The codesign paper treats ``T_alg(p, h, s)`` as an imported black box; only
its interface (parameters + feasibility constraints, eqs. 9-15) is given.
This module re-derives a documented hybrid-hexagonal-tiling time model with
the same interface:

problem parameters  p = (S1, S2[, S3], T)        -- iteration-space extents
hardware parameters h = (n_SM, n_V, M_SM)        -- + GPU family constants
software parameters s = (t_S1, t_S2[, t_S3], t_T, k)

Model (all floor/ceil kept -- the paper's non-smoothness is intentional):

* hexagonal tiles on the (T, S1) plane: average width ``W = t_S1 + s*t_T``
  (sigma = stencil radius), max width ``W_max = t_S1 + 2*s*t_T``;
* a tile is one threadblock of ``t_S2`` threads (mult. of 32 = warps);
  for 3D stencils each thread additionally walks ``t_S3`` points;
* compute time per co-resident *group* (the k blocks hyperthreaded on one
  SM): ``C_iter * t_T * W * t_S3 * ceil(k*t_S2/n_V)`` -- the k*t_S2 resident
  threads time-share the n_V lanes; the group completes k tiles in that
  time, so throughput saturates at ``n_V/C_iter`` points/s/SM exactly when
  ``k*t_S2`` is a multiple of ``n_V`` (latency hiding = rounding efficiency);
* shared-memory footprint / tile (bytes):
  ``n_arr * (W_max+2s) * (t_S2+2s) * (t_S3+2s | 1) * 4``; feasibility is
  eq. (11): ``k * footprint <= M_SM`` (eq. (9) is this divided by k);
* per wavefront *phase* (hexagonal schedules alternate 2 phases per time
  band): ``tiles_phase = ceil(ceil(S1/W)/2) * ceil(S2/t_S2) * ceil(S3/t_S3)``
  tiles issue in batches of ``k*n_SM``; a batch overlaps compute with the
  global-memory traffic of its tiles through the shared bandwidth:
  ``T_batch = max(T_compute_tile, n_active*footprint/BW)``;
* ``T_alg = 2*ceil(T/t_T) * (batches*T_batch + launch_overhead)``.

Every evaluation function is *backend-generic*: it takes an array namespace
``xp`` (``numpy`` by default, ``jax.numpy`` for the JIT-compiled sweep
engine in :mod:`repro.core.sweep`) and only uses ops both provide. The only
Python-level branches are on **static** stencil structure (``st.dims``),
never on array values, so the functions trace cleanly under ``jax.jit`` /
``jax.vmap`` while staying bit-compatible with the seed's NumPy float64
path when called with the defaults.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

import numpy as np

__all__ = [
    "StencilSpec",
    "GPUSpec",
    "ProblemSize",
    "STENCILS",
    "MAXWELL_GPU",
    "TITANX_GPU",
    "GPUS_BY_NAME",
    "footprint_bytes",
    "stencil_time",
    "stencil_gflops",
    "feasible",
    "with_machine_params",
    "with_c_iter",
]


@dataclasses.dataclass(frozen=True)
class StencilSpec:
    """Workload characterization of one stencil benchmark."""

    name: str
    dims: int  # spatial dimensions (2 or 3)
    radius: int  # sigma: halo width per time step
    flops_per_point: float
    n_arrays: int  # arrays resident in the tile footprint (in + out)
    c_iter: float  # seconds per iteration per thread (measured, §IV.B)


@dataclasses.dataclass(frozen=True)
class GPUSpec:
    """Family constants that are *not* design variables (paper §IV.A)."""

    name: str
    bw_gmem: float  # global-memory bandwidth, bytes/s
    max_threads_per_block: int = 1024
    max_threads_per_sm: int = 2048
    max_threadblocks_per_sm: int = 32  # MTB_SM, eq. (10)
    launch_overhead: float = 5.0e-6  # per-phase sync/launch, seconds
    bytes_per_word: int = 4  # fp32 stencils


@dataclasses.dataclass(frozen=True)
class ProblemSize:
    """Problem parameters p. ``s3 = 1`` for 2D stencils.

    Fields are ints for concrete sizes, but the sweep engine may carry JAX
    tracers here (sizes are *dynamic* under jit so one compiled sweep serves
    every problem size) -- hence nothing below hashes or int()-casts them
    except the convenience :attr:`points` property.
    """

    s1: int
    s2: int
    t: int
    s3: int = 1

    @property
    def points(self) -> float:
        return float(self.s1) * self.s2 * self.s3 * self.t


# ---------------------------------------------------------------------------
# The paper's six-benchmark suite (§IV.A). flops/point follow the loop bodies
# of the standard PolyBench/HHC kernels; C_iter is the measured per-iteration
# per-thread cost on the GTX-980 (paper §IV.B: "we measured this parameter
# for the different stencils ... we used the former [GTX-980] value"). The
# published values are not in the paper; these are calibrated so the stock
# GTX-980 / Titan X land in Table II's GFLOP/s magnitude range.
# ---------------------------------------------------------------------------
STENCILS: Dict[str, StencilSpec] = {
    "jacobi2d": StencilSpec("jacobi2d", 2, 1, 5.0, 2, 4.0e-9),
    "heat2d": StencilSpec("heat2d", 2, 1, 10.0, 2, 5.5e-9),
    "laplacian2d": StencilSpec("laplacian2d", 2, 1, 6.0, 2, 4.0e-9),
    "gradient2d": StencilSpec("gradient2d", 2, 1, 9.0, 2, 4.5e-9),
    "heat3d": StencilSpec("heat3d", 3, 1, 15.0, 2, 7.0e-9),
    "laplacian3d": StencilSpec("laplacian3d", 3, 1, 8.0, 2, 6.0e-9),
}

MAXWELL_GPU = GPUSpec(name="gtx980", bw_gmem=224.0e9)
TITANX_GPU = GPUSpec(name="titanx", bw_gmem=336.0e9)

#: THE name -> datasheet-spec registry. Every layer that resolves a GPU
#: family by name (the service CLI's --gpu knob, the calibration fit's
#: measurement-frame lookup) consumes this one table; adding a target
#: means adding it here (plus a stock hardware point in
#: repro.measure.harness if it will frame measurements).
GPUS_BY_NAME: Dict[str, GPUSpec] = {g.name: g for g in (MAXWELL_GPU, TITANX_GPU)}


def with_machine_params(gpu: GPUSpec, bw_gmem=None, launch_overhead=None, name=None):
    """A copy of ``gpu`` with refitted *measured* machine parameters.

    This is the calibration seam (:mod:`repro.measure.calibrate`): the two
    continuous constants the empirical fit can move -- global-memory
    bandwidth and launch overhead -- swapped without touching the design
    variables or family limits. Values may be JAX tracers (the fit
    differentiates straight through :func:`stencil_time` on a spec built
    from traced parameters, exactly like the sweep engine's traced specs).
    """
    updates: Dict[str, object] = {}
    if bw_gmem is not None:
        updates["bw_gmem"] = bw_gmem
    if launch_overhead is not None:
        updates["launch_overhead"] = launch_overhead
    if name is not None:
        updates["name"] = name
    return dataclasses.replace(gpu, **updates)


def with_c_iter(st: StencilSpec, c_iter):
    """A copy of ``st`` with a refitted per-iteration compute cost (the
    per-stencil machine parameter the paper measures in §IV.B). ``c_iter``
    may be a JAX tracer during fitting."""
    return dataclasses.replace(st, c_iter=c_iter)


def _dtype_for(xp, dtype):
    """Default working dtype: float64 on NumPy (seed-exact), float32 on JAX
    backends (float64 would silently downcast unless x64 mode is on)."""
    if dtype is not None:
        return dtype
    return np.float64 if xp is np else np.float32


def _ceil_div(xp, a, b):
    """ceil(a / b) for integer-valued a, b >= 1, exact even where the
    backend's division is not correctly rounded (the TPU's f32 divide
    differs from the host's in the last bit on some quotients; one ulp
    above an exact integer quotient, ``ceil`` would count one tile too
    many): the floor of the quotient is corrected by one integer
    comparison."""
    q = xp.floor(a / b)
    return q + (q * b < a)


def footprint_bytes(st: StencilSpec, gpu: GPUSpec, t_s1, t_s2, t_t, t_s3=1, *, xp=np, dtype=None):
    """Shared-memory bytes needed by one tile (halo-expanded, all arrays)."""
    dtype = _dtype_for(xp, dtype)
    s = st.radius
    t_s1 = xp.asarray(t_s1, dtype)
    t_s2 = xp.asarray(t_s2, dtype)
    t_t = xp.asarray(t_t, dtype)
    t_s3 = xp.asarray(t_s3, dtype)
    w_max = t_s1 + 2.0 * s * t_t
    # static branch on stencil structure -- never on array values
    depth = t_s3 + 2.0 * s if st.dims == 3 else xp.ones_like(t_s3)
    return (
        st.n_arrays
        * (w_max + 2.0 * s)
        * (t_s2 + 2.0 * s)
        * depth
        * gpu.bytes_per_word
    )


def feasible(
    st: StencilSpec,
    gpu: GPUSpec,
    n_sm,
    n_v,
    m_sm,
    t_s1,
    t_s2,
    t_t,
    k,
    t_s3=1,
    *,
    xp=np,
    dtype=None,
):
    """Feasibility mask, eqs. (9)-(15). Broadcasts over array inputs."""
    dtype = _dtype_for(xp, dtype)
    t_s2 = xp.asarray(t_s2, dtype)
    t_t = xp.asarray(t_t, dtype)
    k = xp.asarray(k, dtype)
    fp = footprint_bytes(st, gpu, t_s1, t_s2, t_t, t_s3, xp=xp, dtype=dtype)
    ok = k * fp <= xp.asarray(m_sm, dtype) * 1024.0  # eq. (11) [& (9)]
    ok &= k <= gpu.max_threadblocks_per_sm  # eq. (10)
    ok &= t_s2 <= gpu.max_threads_per_block
    ok &= k * t_s2 <= gpu.max_threads_per_sm
    ok &= t_t % 2 == 0  # eq. (15): t_T even (HHC)
    ok &= t_s2 % 32 == 0  # eq. (13): full warps
    return ok


def stencil_time(
    st: StencilSpec,
    gpu: GPUSpec,
    size: ProblemSize,
    n_sm,
    n_v,
    m_sm,
    t_s1,
    t_s2,
    t_t,
    k,
    t_s3=1,
    *,
    xp=np,
    dtype=None,
):
    """T_alg in seconds. Infeasible points get +inf. Fully vectorized, and
    traceable under jit/vmap when called with ``xp=jax.numpy``."""
    dtype = _dtype_for(xp, dtype)
    n_sm = xp.asarray(n_sm, dtype)
    n_v = xp.asarray(n_v, dtype)
    t_s1 = xp.asarray(t_s1, dtype)
    t_s2 = xp.asarray(t_s2, dtype)
    t_t = xp.asarray(t_t, dtype)
    k = xp.asarray(k, dtype)
    t_s3 = xp.asarray(t_s3, dtype)
    s1 = xp.asarray(size.s1, dtype)
    s2 = xp.asarray(size.s2, dtype)
    s3 = xp.asarray(size.s3, dtype)
    t_total = xp.asarray(size.t, dtype)
    s = st.radius

    w_avg = t_s1 + s * t_t
    fp = footprint_bytes(st, gpu, t_s1, t_s2, t_t, t_s3, xp=xp, dtype=dtype)

    # --- compute time of one co-resident group (k blocks -> k tiles done).
    serial = xp.ceil(k * t_s2 / n_v)
    t_compute = st.c_iter * t_t * w_avg * t_s3 * serial

    # --- phase structure.
    tiles_phase = (
        xp.ceil(_ceil_div(xp, s1, w_avg) / 2.0)
        * _ceil_div(xp, s2, t_s2)
        * (_ceil_div(xp, s3, t_s3) if st.dims == 3 else 1.0)
    )
    tiles_phase = xp.maximum(tiles_phase, 1.0)
    concurrent = xp.minimum(k * n_sm, tiles_phase)
    batches = _ceil_div(xp, tiles_phase, k * n_sm)

    # --- per-batch: all concurrent tiles' global traffic shares BW.
    t_mem = concurrent * fp / gpu.bw_gmem
    t_batch = xp.maximum(t_compute, t_mem)

    phases = 2.0 * _ceil_div(xp, t_total, t_t)
    t_alg = phases * (batches * t_batch + gpu.launch_overhead)

    ok = feasible(
        st, gpu, n_sm, n_v, m_sm, t_s1, t_s2, t_t, k, t_s3, xp=xp, dtype=dtype
    )
    return xp.where(ok, t_alg, xp.inf)


def stencil_gflops(st: StencilSpec, size: ProblemSize, t_alg_seconds, *, xp=np):
    """Achieved GFLOP/s given a T_alg (broadcasts)."""
    total = st.flops_per_point * size.points
    return total / xp.asarray(t_alg_seconds) / 1.0e9
