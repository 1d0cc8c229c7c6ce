"""Shared Pallas machinery for the six stencil kernels (the paper's
workload, §IV.A), adapted to the TPU memory hierarchy.

GPU -> TPU adaptation (DESIGN.md, "Hardware adaptation"): the paper's
hybrid-hexagonal GPU tiling streams a (t_S1 x t_S2) tile + halo through
*shared memory* with one thread per S2 column. The TPU-native equivalent
keeps the same software-managed-memory insight but re-blocks for VMEM and
the VPU lane layout:

* the array is blocked along the *leading* spatial dimension into bands of
  ``block_rows`` rows; the trailing dimension stays whole (TPU lanes want
  the last dim contiguous and 128-aligned);
* the halo is realized with *neighbor-band BlockSpecs*: each grid step is
  given three aliased views of the input -- the previous, current and next
  band -- so the kernel never performs unaligned HBM reads; the up/down
  halo rows are the last/first rows of the neighbor bands;
* boundary cells (Dirichlet: borders are copied through) are handled by a
  global-row/column mask computed from the grid position, which also makes
  partially-padded trailing bands safe;
* ``block_rows`` is the software parameter of the codesign problem (the
  analogue of the paper's tile sizes): :func:`plan_block_rows` solves the
  same footprint-feasibility constraint as eqs. (9)/(11) -- resident
  buffers must fit the VMEM budget -- and is what `repro.core` codesign
  selects when it tunes the kernels.

All kernels come in (pallas, reference) pairs; `tests/test_kernels.py`
sweeps shapes/dtypes and asserts allclose in interpret mode (interpret=True
executes the same kernel body on any backend; interpret=False compiles it
for the TPU).
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = [
    "stencil2d_call",
    "stencil3d_call",
    "plan_block_rows",
    "time_loop",
]

#: Mosaic's default scoped-VMEM limit on a TPU v5e is 16 MiB; keep the
#: planned working set under three quarters of it.
VMEM_BUDGET_BYTES = 12 * 1024 * 1024

#: band-sized f32 temporaries a kernel body keeps live (the halo-extended
#: band, its neighbor slices and the update), measured from the compiler's
#: scoped-VMEM report for the 3-D kernels.
_TEMP_BANDS = 8


def plan_block_rows(
    shape, dtype, vmem_bytes: int = VMEM_BUDGET_BYTES, min_rows: int = 8
) -> int:
    """Choose the band height: the eq.-(9)/(11) feasibility solve for TPU.

    Resident working set = 3 input bands + 1 output band, each double
    buffered by the pipeline, plus the body's f32 temporaries over the
    halo-extended band, all of width ``prod(shape[1:])``; pick the largest
    power-of-two row count whose working set fits the VMEM budget.
    """
    width = 1
    for d in shape[1:]:
        width *= int(d)
    io_row = int(jnp.dtype(dtype).itemsize) * width
    f32_row = 4 * width

    def need(rows):
        return 2 * 4 * rows * io_row + _TEMP_BANDS * (rows + 2) * f32_row

    rows = shape[0]
    while rows > min_rows and need(rows) > vmem_bytes:
        rows //= 2
    return max(1, min(rows, shape[0]))


def _edge_pad(v: jax.Array, axes, halo: int) -> jax.Array:
    """Replicate the edge ``halo`` cells outward along ``axes`` -- ``jnp.pad``
    edge mode, built from slices: Mosaic rejects the zero-width pieces that
    ``jnp.pad`` lowers to for an unpadded axis."""
    for axis in axes:
        first = jax.lax.slice_in_dim(v, 0, 1, axis=axis)
        last = jax.lax.slice_in_dim(v, v.shape[axis] - 1, v.shape[axis], axis=axis)
        v = jnp.concatenate([first] * halo + [v] + [last] * halo, axis=axis)
    return v


def _row_mask(i, block_rows: int, n_rows: int, width: int, halo: int):
    """Boolean (block_rows, width) mask of *boundary* cells for this band."""
    gstart = i * block_rows
    rows = gstart + jax.lax.broadcasted_iota(jnp.int32, (block_rows, width), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (block_rows, width), 1)
    return (
        (rows < halo)
        | (rows >= n_rows - halo)
        | (cols < halo)
        | (cols >= width - halo)
    )


def _stencil2d_kernel(
    prev_ref, cur_ref, nxt_ref, out_ref, *, update: Callable, block_rows: int,
    n_rows: int, halo: int
):
    cur = cur_ref[...]
    width = cur.shape[1]
    # halo-extended band: last rows of prev band + cur + first rows of next.
    # Accumulate in f32 (standard TPU practice for bf16 data), store narrow.
    ext = jnp.concatenate(
        [prev_ref[...][-halo:, :], cur, nxt_ref[...][:halo, :]], axis=0
    ).astype(jnp.float32)
    # column halo via edge replication (border cells are masked anyway)
    ext = _edge_pad(ext, (1,), halo)
    new = update(ext, halo)  # (block_rows, width)
    i = pl.program_id(0)
    boundary = _row_mask(i, block_rows, n_rows, width, halo)
    out_ref[...] = jnp.where(boundary, cur, new).astype(out_ref.dtype)


def stencil2d_call(
    x: jax.Array,
    update: Callable,
    halo: int = 1,
    block_rows: int | None = None,
    *,
    interpret: bool,
) -> jax.Array:
    """One stencil step on a 2D array via `pl.pallas_call`.

    ``update(ext, halo)`` receives the halo-extended band (rows+2h, cols+2h)
    and must return the updated interior (rows, cols).
    """
    n_rows, width = x.shape
    if block_rows is None:
        block_rows = plan_block_rows(x.shape, x.dtype)
    block_rows = min(block_rows, n_rows)
    grid = (pl.cdiv(n_rows, block_rows),)
    nblk = grid[0]
    spec = functools.partial(pl.BlockSpec, (block_rows, width))
    kernel = functools.partial(
        _stencil2d_kernel,
        update=update,
        block_rows=block_rows,
        n_rows=n_rows,
        halo=halo,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            spec(lambda i: (jnp.maximum(i - 1, 0), 0)),  # prev band
            spec(lambda i: (i, 0)),  # current band
            spec(lambda i: (jnp.minimum(i + 1, nblk - 1), 0)),  # next band
        ],
        out_specs=spec(lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
    )(x, x, x)


def _stencil3d_kernel(
    prev_ref, cur_ref, nxt_ref, out_ref, *, update: Callable, block_rows: int,
    n_rows: int, halo: int
):
    cur = cur_ref[...]
    _, h, w = cur.shape
    ext = jnp.concatenate(
        [prev_ref[...][-halo:], cur, nxt_ref[...][:halo]], axis=0
    ).astype(jnp.float32)
    ext = _edge_pad(ext, (1, 2), halo)
    new = update(ext, halo)  # (block_rows, h, w)
    i = pl.program_id(0)
    gstart = i * block_rows
    d_ids = gstart + jax.lax.broadcasted_iota(jnp.int32, (block_rows, h, w), 0)
    h_ids = jax.lax.broadcasted_iota(jnp.int32, (block_rows, h, w), 1)
    w_ids = jax.lax.broadcasted_iota(jnp.int32, (block_rows, h, w), 2)
    boundary = (
        (d_ids < halo)
        | (d_ids >= n_rows - halo)
        | (h_ids < halo)
        | (h_ids >= h - halo)
        | (w_ids < halo)
        | (w_ids >= w - halo)
    )
    out_ref[...] = jnp.where(boundary, cur, new).astype(out_ref.dtype)


def stencil3d_call(
    x: jax.Array,
    update: Callable,
    halo: int = 1,
    block_rows: int | None = None,
    *,
    interpret: bool,
) -> jax.Array:
    """One stencil step on a 3D array, blocked along the leading dim."""
    n_rows, h, w = x.shape
    if block_rows is None:
        block_rows = plan_block_rows(x.shape, x.dtype)
    block_rows = min(block_rows, n_rows)
    grid = (pl.cdiv(n_rows, block_rows),)
    nblk = grid[0]
    spec = functools.partial(pl.BlockSpec, (block_rows, h, w))
    kernel = functools.partial(
        _stencil3d_kernel,
        update=update,
        block_rows=block_rows,
        n_rows=n_rows,
        halo=halo,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            spec(lambda i: (jnp.maximum(i - 1, 0), 0, 0)),
            spec(lambda i: (i, 0, 0)),
            spec(lambda i: (jnp.minimum(i + 1, nblk - 1), 0, 0)),
        ],
        out_specs=spec(lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
    )(x, x, x)


def time_loop(step: Callable, x: jax.Array, steps: int) -> jax.Array:
    """Apply ``step`` ``steps`` times (the stencil time dimension T)."""
    if steps == 1:
        return step(x)
    return jax.lax.fori_loop(0, steps, lambda _, v: step(v), x)
