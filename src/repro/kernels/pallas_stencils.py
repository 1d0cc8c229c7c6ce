"""Pallas stencil kernels parameterized by the eq.-18 tile lattice.

The sweep engine (:mod:`repro.core.sweep`) optimizes over software
parameters ``(t_s1, t_s2, t_t, k, t_s3)`` -- but until this module, no
executable kernel accepted those parameters: ``repro.kernels.ops`` exposes
only a VMEM band height (``block_rows``), so the time model's predictions
were never confronted with a kernel actually *running* the tile shapes the
optimizer enumerates. This module closes that gap for the measurement
subsystem (:mod:`repro.measure`):

* a tile is a ``(t_s1, t_s2[, t_s3])`` block of the iteration space; the
  grid covers the array in those blocks (the paper's "one threadblock of
  t_S2 threads per tile" becomes "one grid step per tile");
* ``t_t`` is the *time-tile depth*: one ``pallas_call`` advances up to
  ``t_t`` stencil steps before touching HBM again, reading a halo-extended
  block of ``radius * t_t`` extra cells per side (overlapped -- a.k.a.
  trapezoidal -- time tiling). The paper's hybrid-hexagonal schedule avoids
  the redundant halo compute by alternating phases; the overlapped schedule
  trades that redundancy for independence of tiles, but spans the *same*
  ``(t_s1, t_s2, t_t, t_s3)`` parameter space with the same footprint and
  bandwidth scaling, which is what the calibration fit needs;
* ``k`` (tiles co-resident per SM) is an occupancy/scheduling knob with no
  effect on values; it is accepted (so a full sweep-lattice point is a
  valid tile config) and ignored by the kernel body;
* Dirichlet borders and out-of-tile padding are handled by masking on
  *global* coordinates, so any tile shape -- aligned or not, larger than
  the array or not -- is value-identical to the reference
  (:mod:`repro.kernels.ref`); ``tests/test_pallas_stencils.py`` asserts
  allclose (f32 accumulation, atol/rtol 1e-5) across the tile grid in
  ``interpret=True`` mode on CPU.

Correctness of the time tile: after ``n`` in-kernel steps the outer
``radius*n`` ring of the halo-extended block is stale (it read replicated
edge values), but the core tile sits ``radius*t_t`` cells from the block
edge, so every core value equals the global evolution. Boundary cells are
pinned by the mask (Dirichlet), and padding cells are only ever read by
pinned cells, so they cannot leak in.

The input rides into the kernel as one unblocked ref and each grid step
slices its own halo-extended window with ``pl.ds`` -- overlapping reads
that blocked ``BlockSpec`` indexing cannot express. That keeps the whole
array resident per step, which is exactly right for the measurement
harness's problem sizes; a variant for larger grids would stream windows
by DMA instead.

Compiled for a TPU (``interpret=False``), the output block must be a
legal Mosaic block: its last two dimensions divisible by 8 (sublanes) and
128 (lanes), or equal to the whole padded extent. ``t_s1`` blocks the
sublane axis and ``t_s2`` the lane axis in both dimensionalities -- the
model's ``t_s2`` is the thread (coalesced) axis, which is what TPU lanes
are -- so 3-D grids run in a ``(s3, s1, s2)`` layout and ``t_s3`` blocks
the leading axis, where any size is legal. :func:`chip_tile_error` states
the rule; :func:`run_tiled` raises it before lowering.
"""

from __future__ import annotations

import functools
from types import ModuleType
from typing import Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import gradient2d, heat2d, heat3d, jacobi2d, laplacian2d, laplacian3d

__all__ = [
    "TILE_NAMES",
    "DEFAULT_TILES",
    "chip_tile_error",
    "normalize_tiles",
    "tile_footprint_cells",
    "stencil_run_tiled",
    "run_tiled",
]

#: software-parameter order -- MUST stay aligned with
#: ``repro.core.sweep.SW_NAMES`` (asserted in tests): a packed (5,) row
#: from the sweep's refine path is a valid tile config here.
TILE_NAMES = ("t_s1", "t_s2", "t_t", "k", "t_s3")

#: a modest default, model-feasible at the stock hardware points for every
#: stencil and a legal TPU block for every shape.
DEFAULT_TILES = {"t_s1": 8, "t_s2": 128, "t_t": 2, "k": 1, "t_s3": 2}

#: Mosaic's block-shape rule: (sublane, lane) multiples of the last two
#: block dimensions, unless a block spans the whole padded extent.
SUBLANES, LANES = 8, 128

_MODULES: Dict[str, ModuleType] = {
    m.NAME: m
    for m in (jacobi2d, heat2d, laplacian2d, gradient2d, heat3d, laplacian3d)
}


def normalize_tiles(tiles: Optional[Mapping[str, int]]) -> Tuple[int, ...]:
    """Tile mapping -> hashable ``TILE_NAMES``-ordered int tuple (the jit
    static key). Unknown names and non-positive sizes are rejected here so
    a typo'd sweep row fails loudly, not as a silent default."""
    merged = dict(DEFAULT_TILES)
    if tiles:
        unknown = set(tiles) - set(TILE_NAMES)
        if unknown:
            raise ValueError(
                f"unknown tile parameter(s) {sorted(unknown)} "
                f"(want {list(TILE_NAMES)})"
            )
        merged.update({k: int(v) for k, v in tiles.items()})
    out = tuple(int(merged[k]) for k in TILE_NAMES)
    if any(v < 1 for v in out):
        raise ValueError(f"tile sizes must be >= 1, got {dict(zip(TILE_NAMES, out))}")
    return out


def chip_tile_error(shape: Tuple[int, ...], tiles: Mapping[str, int]) -> Optional[str]:
    """Why a TPU cannot compile this tile on an array of ``shape``, or None.

    ``t_s1`` must be a multiple of 8 and ``t_s2`` a multiple of 128, each
    unless one tile covers that axis whole (``t >= extent``)."""
    t = dict(zip(TILE_NAMES, normalize_tiles(tiles)))
    bad = [
        f"{name}={t[name]} (multiple of {quantum}, or >= the extent {extent})"
        for name, quantum, extent in (
            ("t_s1", SUBLANES, shape[0]), ("t_s2", LANES, shape[1]),
        )
        if t[name] % quantum and t[name] < extent
    ]
    if not bad:
        return None
    return (
        f"tile not a legal TPU block on shape {tuple(shape)}: "
        + ", ".join(bad) + "; use interpret=True for arbitrary tiles"
    )


def tile_footprint_cells(dims: int, tiles: Mapping[str, int], radius: int = 1) -> int:
    """Cells resident per halo-extended time tile -- the empirical analogue
    of :func:`repro.core.timemodel.footprint_bytes` (divide by arrays x
    bytes/word to compare orders of magnitude, not exact constants)."""
    t = dict(zip(TILE_NAMES, normalize_tiles(tiles)))
    hh = radius * t["t_t"]
    cells = (t["t_s1"] + 2 * hh) * (t["t_s2"] + 2 * hh)
    if dims == 3:
        cells *= t["t_s3"] + 2 * hh
    return int(cells)


# ---------------------------------------------------------------------------
# kernel bodies
# ---------------------------------------------------------------------------
def _kernel_2d(x_ref, out_ref, *, update, radius, hh, t_s1, t_s2, n_steps, s1, s2):
    i, j = pl.program_id(0), pl.program_id(1)
    er, ec = t_s1 + 2 * hh, t_s2 + 2 * hh
    ext = x_ref[pl.ds(i * t_s1, er), pl.ds(j * t_s2, ec)].astype(jnp.float32)
    # global (unpadded) coordinates of every ext cell: the Dirichlet mask
    # and the padding guard in one predicate
    rows = i * t_s1 - hh + jax.lax.broadcasted_iota(jnp.int32, (er, ec), 0)
    cols = j * t_s2 - hh + jax.lax.broadcasted_iota(jnp.int32, (er, ec), 1)
    active = (
        (rows >= radius) & (rows < s1 - radius)
        & (cols >= radius) & (cols < s2 - radius)
    )

    def one_step(_, v):
        vp = jnp.pad(v, radius, mode="edge")
        return jnp.where(active, update(vp, radius), v)

    ext = jax.lax.fori_loop(0, n_steps, one_step, ext)
    out_ref[...] = ext[hh : hh + t_s1, hh : hh + t_s2].astype(out_ref.dtype)


def _kernel_3d(x_ref, out_ref, *, update, radius, hh, blocks, n_steps, extents):
    """3-D body over generic array axes: ``blocks``/``extents`` are given in
    the array's own axis order (the ``(s3, s1, s2)`` layout of
    :func:`stencil_run_tiled`); the stencils are symmetric in their axes."""
    b0, b1, b2 = blocks
    ids = (pl.program_id(0), pl.program_id(1), pl.program_id(2))
    shape = tuple(b + 2 * hh for b in blocks)
    ext = x_ref[
        pl.ds(ids[0] * b0, shape[0]),
        pl.ds(ids[1] * b1, shape[1]),
        pl.ds(ids[2] * b2, shape[2]),
    ].astype(jnp.float32)
    active = None
    for axis, (g, b, n) in enumerate(zip(ids, blocks, extents)):
        d = g * b - hh + jax.lax.broadcasted_iota(jnp.int32, shape, axis)
        inside = (d >= radius) & (d < n - radius)
        active = inside if active is None else active & inside

    def one_step(_, v):
        vp = jnp.pad(v, radius, mode="edge")
        return jnp.where(active, update(vp, radius), v)

    ext = jax.lax.fori_loop(0, n_steps, one_step, ext)
    out_ref[...] = ext[hh : hh + b0, hh : hh + b1, hh : hh + b2].astype(out_ref.dtype)


# ---------------------------------------------------------------------------
# pass drivers (one pallas_call = up to t_t time steps)
# ---------------------------------------------------------------------------
def _pass_2d(x, update, radius, t_s1, t_s2, n_steps, interpret):
    s1, s2 = x.shape
    hh = radius * n_steps
    g1, g2 = pl.cdiv(s1, t_s1), pl.cdiv(s2, t_s2)
    rows_p, cols_p = g1 * t_s1, g2 * t_s2
    xp = jnp.pad(x, ((hh, hh + rows_p - s1), (hh, hh + cols_p - s2)), mode="edge")
    kernel = functools.partial(
        _kernel_2d, update=update, radius=radius, hh=hh,
        t_s1=t_s1, t_s2=t_s2, n_steps=n_steps, s1=s1, s2=s2,
    )
    out = pl.pallas_call(
        kernel,
        grid=(g1, g2),
        in_specs=[pl.BlockSpec(xp.shape, lambda i, j: (0, 0))],
        out_specs=pl.BlockSpec((t_s1, t_s2), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rows_p, cols_p), x.dtype),
        interpret=interpret,
    )(xp)
    return out[:s1, :s2]


def _pass_3d(x, update, radius, blocks, n_steps, interpret):
    """One pass over a 3-D array, ``blocks`` in the array's axis order."""
    extents = x.shape
    hh = radius * n_steps
    grid = tuple(pl.cdiv(n, b) for n, b in zip(extents, blocks))
    padded = tuple(g * b for g, b in zip(grid, blocks))
    xp = jnp.pad(
        x, [(hh, hh + p - n) for p, n in zip(padded, extents)], mode="edge"
    )
    kernel = functools.partial(
        _kernel_3d, update=update, radius=radius, hh=hh,
        blocks=blocks, n_steps=n_steps, extents=extents,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec(xp.shape, lambda i, j, m: (0, 0, 0))],
        out_specs=pl.BlockSpec(blocks, lambda i, j, m: (i, j, m)),
        out_shape=jax.ShapeDtypeStruct(padded, x.dtype),
        interpret=interpret,
    )(xp)
    return out[: extents[0], : extents[1], : extents[2]]


@functools.partial(
    jax.jit, static_argnames=("name", "steps", "tiles", "interpret")
)
def stencil_run_tiled(
    name: str,
    x: jax.Array,
    steps: int,
    tiles: Tuple[int, ...],
    interpret: bool,
) -> jax.Array:
    """Jitted T-step run at one (normalized) tile tuple -- the harness's
    hot entry point. ``tiles`` must come from :func:`normalize_tiles`."""
    mod = _MODULES[name]
    t_s1, t_s2, t_t, _k, t_s3 = tiles
    radius = mod.HALO
    if mod.DIMS == 3:  # (s1, s2, s3) -> (s3, s1, s2): t_s2 on the lanes
        x = jnp.moveaxis(x, 2, 0)
    done = 0
    while done < steps:
        n = min(t_t, steps - done)
        if mod.DIMS == 3:
            x = _pass_3d(x, mod.update, radius, (t_s3, t_s1, t_s2), n, interpret)
        else:
            x = _pass_2d(x, mod.update, radius, t_s1, t_s2, n, interpret)
        done += n
    return jnp.moveaxis(x, 0, 2) if mod.DIMS == 3 else x


def run_tiled(
    name: str,
    x: jax.Array,
    steps: int = 1,
    tiles: Optional[Mapping[str, int]] = None,
    *,
    interpret: bool,
) -> jax.Array:
    """T time steps of the named stencil at an eq.-18 tile configuration.

    ``tiles`` maps any subset of :data:`TILE_NAMES` to ints (sweep rows,
    ``decode_index`` dicts, and ``decode_sw`` dicts all qualify); missing
    parameters take :data:`DEFAULT_TILES`. ``interpret=True`` runs the
    kernel body in the Pallas interpreter (any backend, any tile);
    ``interpret=False`` compiles it for the TPU, and a tile that is not a
    legal TPU block raises :class:`ValueError` (:func:`chip_tile_error`).
    """
    if name not in _MODULES:
        raise KeyError(f"unknown stencil {name!r} (want one of {sorted(_MODULES)})")
    if steps < 0:
        raise ValueError(f"steps must be >= 0, got {steps}")
    if not interpret:
        err = chip_tile_error(x.shape, tiles)
        if err:
            raise ValueError(err)
    if steps == 0:
        return x
    return stencil_run_tiled(
        name, x, int(steps), normalize_tiles(tiles), bool(interpret)
    )
