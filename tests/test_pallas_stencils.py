"""Tile-parameterized Pallas stencils vs the independent jnp oracle
(`kernels/ref.py`), across the eq.-18 tile lattice, in interpret mode on
CPU -- the tentpole equivalence property: every sweep-enumerable tile
configuration reproduces the reference evolution to f32 accumulation
accuracy (see :func:`assert_close` for the documented tolerance)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st  # soft dep: skips, not errors

from repro.kernels.pallas_stencils import (
    DEFAULT_TILES,
    TILE_NAMES,
    chip_tile_error,
    normalize_tiles,
    run_tiled,
    tile_footprint_cells,
)
from repro.kernels.ref import run_ref

NAMES_2D = ["jacobi2d", "heat2d", "laplacian2d", "gradient2d"]
NAMES_3D = ["heat3d", "laplacian3d"]

#: a slice of the sweep lattice (repro.core.solver.LATTICE_2D/3D values),
#: deliberately including tiles larger than the arrays, t_s1=1 strips, and
#: time tiles deeper than the run.
TILE_GRID_2D = [
    {"t_s1": 1, "t_s2": 32, "t_t": 2, "k": 1},
    {"t_s1": 4, "t_s2": 32, "t_t": 4, "k": 8},
    {"t_s1": 8, "t_s2": 64, "t_t": 2, "k": 2},
    {"t_s1": 16, "t_s2": 128, "t_t": 8, "k": 32},
    {"t_s1": 64, "t_s2": 1024, "t_t": 2, "k": 1},
]
TILE_GRID_3D = [
    {"t_s1": 1, "t_s2": 32, "t_t": 2, "k": 1, "t_s3": 1},
    {"t_s1": 4, "t_s2": 32, "t_t": 2, "k": 4, "t_s3": 2},
    {"t_s1": 8, "t_s2": 64, "t_t": 4, "k": 1, "t_s3": 8},
    {"t_s1": 32, "t_s2": 256, "t_t": 6, "k": 16, "t_s3": 4},
]

def _rand(shape, dtype=jnp.float32, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, dtype=jnp.float32).astype(dtype)


def assert_close(got, want, rtol=1e-4):
    """The documented equivalence tolerance: rtol=1e-4 elementwise plus an
    absolute slack of rtol x the field magnitude. Both sides accumulate in
    f32 but sum neighbor terms in different orders (tile-local vs whole
    array), and laplacian/gradient iterations amplify the field by orders
    of magnitude per step, so rounding differences compound relative to
    the *field* scale, not each cell's value. Single steps agree to
    ~1e-7; this bound holds across the tile grid and multi-step runs."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    scale = float(np.max(np.abs(want))) if want.size else 1.0
    np.testing.assert_allclose(got, want, rtol=rtol, atol=rtol * max(1.0, scale))


def test_tile_names_match_sweep_order():
    """A packed sweep row (refine_points / decode_sw output) must be a
    valid tile config positionally -- the whole point of sharing names."""
    from repro.core.sweep import SW_NAMES

    assert TILE_NAMES == SW_NAMES


@pytest.mark.parametrize("name", NAMES_2D)
@pytest.mark.parametrize("tiles", TILE_GRID_2D)
def test_2d_tile_grid_matches_oracle(name, tiles):
    x = _rand((37, 53), seed=1)
    got = run_tiled(name, x, steps=5, tiles=tiles, interpret=True)
    want = run_ref(name, x, steps=5)
    assert_close(got, want)


@pytest.mark.parametrize("name", NAMES_3D)
@pytest.mark.parametrize("tiles", TILE_GRID_3D)
def test_3d_tile_grid_matches_oracle(name, tiles):
    x = _rand((11, 13, 17), seed=2)
    got = run_tiled(name, x, steps=4, tiles=tiles, interpret=True)
    want = run_ref(name, x, steps=4)
    assert_close(got, want)


@pytest.mark.parametrize("name", ["heat2d", "heat3d"])
def test_bf16_inputs_upcast_like_reference(name):
    shape = (24, 40) if name == "heat2d" else (10, 12, 14)
    x = _rand(shape, jnp.bfloat16, seed=3)
    got = run_tiled(name, x, steps=2, tiles={"t_s1": 8, "t_s2": 32, "t_t": 2},
                   interpret=True)
    want = run_ref(name, x, steps=2)
    assert got.dtype == x.dtype
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=2e-2, atol=2e-2,
    )


def test_k_is_occupancy_only():
    """k (blocks co-resident per SM) schedules, never computes: results are
    identical across k."""
    x = _rand((29, 31), seed=4)
    outs = [
        np.asarray(run_tiled("jacobi2d", x, steps=3,
                             tiles={"t_s1": 8, "t_s2": 32, "t_t": 2, "k": k},
                             interpret=True))
        for k in (1, 8, 32)
    ]
    for o in outs[1:]:
        np.testing.assert_array_equal(outs[0], o)


def test_time_tile_depth_is_semantics_preserving():
    """Any t_t splits the same T steps into passes; values must agree."""
    x = _rand((25, 45), seed=5)
    want = run_ref("heat2d", x, steps=7)
    for t_t in (1, 2, 3, 7, 16):
        got = run_tiled("heat2d", x, steps=7,
                        tiles={"t_s1": 8, "t_s2": 32, "t_t": t_t}, interpret=True)
        assert_close(got, want)


def test_borders_are_dirichlet():
    x = _rand((18, 22), seed=6)
    y = run_tiled("laplacian2d", x, steps=3,
                  tiles={"t_s1": 4, "t_s2": 32, "t_t": 2}, interpret=True)
    np.testing.assert_array_equal(np.asarray(y[0]), np.asarray(x[0]))
    np.testing.assert_array_equal(np.asarray(y[-1]), np.asarray(x[-1]))
    np.testing.assert_array_equal(np.asarray(y[:, 0]), np.asarray(x[:, 0]))
    np.testing.assert_array_equal(np.asarray(y[:, -1]), np.asarray(x[:, -1]))


def test_normalize_tiles_contract():
    assert normalize_tiles(None) == tuple(DEFAULT_TILES[k] for k in TILE_NAMES)
    assert normalize_tiles({"t_s1": 2})[0] == 2
    with pytest.raises(ValueError, match="unknown tile parameter"):
        normalize_tiles({"t_sX": 2})
    with pytest.raises(ValueError, match=">= 1"):
        normalize_tiles({"t_t": 0})
    with pytest.raises(KeyError, match="unknown stencil"):
        run_tiled("nosuch", jnp.zeros((4, 4)), steps=1, interpret=True)
    with pytest.raises(ValueError, match="steps"):
        run_tiled("heat2d", jnp.zeros((4, 4)), steps=-1, interpret=True)


def test_zero_steps_is_identity():
    x = _rand((9, 9), seed=7)
    assert run_tiled("heat2d", x, steps=0, interpret=True) is x


def test_footprint_grows_with_time_tile():
    small = tile_footprint_cells(2, {"t_s1": 8, "t_s2": 32, "t_t": 2})
    deep = tile_footprint_cells(2, {"t_s1": 8, "t_s2": 32, "t_t": 8})
    assert deep > small
    assert tile_footprint_cells(3, {"t_s1": 8, "t_s2": 32, "t_t": 2}) > small


@settings(max_examples=12, deadline=None)
@given(
    name=st.sampled_from(NAMES_2D),
    rows=st.integers(3, 40),
    cols=st.integers(3, 60),
    t_s1=st.integers(1, 16),
    t_s2=st.sampled_from([32, 64]),
    t_t=st.integers(1, 5),
    steps=st.integers(1, 6),
)
def test_property_2d_any_tile_allclose(name, rows, cols, t_s1, t_s2, t_t, steps):
    x = _rand((rows, cols), seed=rows * cols)
    got = run_tiled(name, x, steps=steps,
                    tiles={"t_s1": t_s1, "t_s2": t_s2, "t_t": t_t}, interpret=True)
    want = run_ref(name, x, steps=steps)
    assert_close(got, want)


@pytest.mark.parametrize(
    "shape,tiles,legal",
    [
        ((1024, 1024), DEFAULT_TILES, True),
        ((96, 96, 96), DEFAULT_TILES, True),  # t_s3 blocks the leading axis
        ((1024, 1024), {"t_s1": 8, "t_s2": 32}, False),  # lanes want 128
        ((1024, 1024), {"t_s1": 4, "t_s2": 128}, False),  # sublanes want 8
        ((37, 53), {"t_s1": 64, "t_s2": 64}, True),  # one tile, whole extent
        ((37, 53), {"t_s1": 5, "t_s2": 128}, False),
    ],
)
def test_chip_tile_rule(shape, tiles, legal):
    err = chip_tile_error(shape, tiles)
    assert (err is None) == legal
    if not legal:
        assert "multiple of" in err


def test_compiled_run_rejects_illegal_tile_before_lowering():
    """interpret=False with a tile the TPU cannot block raises the rule
    itself (on any backend -- nothing is lowered), never a skip."""
    x = _rand((64, 256), seed=8)
    with pytest.raises(ValueError, match="t_s2=32 \\(multiple of 128"):
        run_tiled("heat2d", x, steps=2, tiles={"t_s2": 32}, interpret=False)
