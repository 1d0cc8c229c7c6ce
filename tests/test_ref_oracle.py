"""Hardening for `kernels/ref.py` -- the oracle every Pallas kernel
(banded and tile-parameterized) is equivalence-tested against.

The cross-check here is a third, maximally-dumb implementation: explicit
Python loops over cells in NumPy float64, written from the stencils'
mathematical definitions (module docstrings), sharing no code with either
the jnp oracle or the kernels. Coverage: odd/degenerate shapes and both
float32/float64 inputs (the latter under JAX's x64 mode) -- the contract
being that ref computes in f32 regardless of input dtype and stores back
in the input dtype."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ops import stencil_step
from repro.kernels.ref import REF_STEPS, run_ref

NAMES_2D = ["jacobi2d", "heat2d", "laplacian2d", "gradient2d"]
NAMES_3D = ["heat3d", "laplacian3d"]

ODD_SHAPES_2D = [(3, 3), (5, 7), (9, 3), (4, 3), (7, 13), (2, 5)]
ODD_SHAPES_3D = [(3, 3, 3), (5, 3, 7), (7, 7, 5), (3, 4, 5)]


def _loop_step_2d(name: str, x: np.ndarray) -> np.ndarray:
    """One step, scalar loops, float64 -- independent of ref.py's slicing."""
    x = np.asarray(x, np.float64)
    y = x.copy()
    n_r, n_c = x.shape
    for i in range(1, n_r - 1):
        for j in range(1, n_c - 1):
            c = x[i, j]
            n = x[i - 1, j]
            s = x[i + 1, j]
            w = x[i, j - 1]
            e = x[i, j + 1]
            if name == "jacobi2d":
                y[i, j] = 0.2 * (c + n + s + e + w)
            elif name == "heat2d":
                y[i, j] = c + 0.125 * (n + s + e + w - 4.0 * c)
            elif name == "laplacian2d":
                y[i, j] = n + s + e + w - 4.0 * c
            elif name == "gradient2d":
                gx = 0.5 * (e - w)
                gy = 0.5 * (s - n)
                y[i, j] = np.sqrt(gx * gx + gy * gy)
            else:
                raise AssertionError(name)
    return y


def _loop_step_3d(name: str, x: np.ndarray) -> np.ndarray:
    x = np.asarray(x, np.float64)
    y = x.copy()
    d, h, w = x.shape
    for i in range(1, d - 1):
        for j in range(1, h - 1):
            for k in range(1, w - 1):
                c = x[i, j, k]
                neighbors = (
                    x[i - 1, j, k] + x[i + 1, j, k]
                    + x[i, j - 1, k] + x[i, j + 1, k]
                    + x[i, j, k - 1] + x[i, j, k + 1]
                )
                if name == "heat3d":
                    y[i, j, k] = c + 0.125 * (neighbors - 6.0 * c)
                elif name == "laplacian3d":
                    y[i, j, k] = neighbors - 6.0 * c
                else:
                    raise AssertionError(name)
    return y


def _loop_run(name: str, x: np.ndarray, steps: int) -> np.ndarray:
    step = _loop_step_3d if name in NAMES_3D else _loop_step_2d
    for _ in range(steps):
        x = step(name, x)
    return x


def _rand(shape, seed=0):
    rng = np.random.default_rng(seed)
    return rng.normal(size=shape)


TOL = dict(rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("name", NAMES_2D)
@pytest.mark.parametrize("shape", ODD_SHAPES_2D)
def test_ref_2d_matches_scalar_loops_float32(name, shape):
    x = _rand(shape, seed=sum(shape))
    got = run_ref(name, jnp.asarray(x, jnp.float32), steps=2)
    want = _loop_run(name, x, steps=2)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got, np.float64), want, **TOL)


@pytest.mark.parametrize("name", NAMES_3D)
@pytest.mark.parametrize("shape", ODD_SHAPES_3D)
def test_ref_3d_matches_scalar_loops_float32(name, shape):
    x = _rand(shape, seed=sum(shape))
    got = run_ref(name, jnp.asarray(x, jnp.float32), steps=2)
    want = _loop_run(name, x, steps=2)
    np.testing.assert_allclose(np.asarray(got, np.float64), want, **TOL)


@pytest.mark.parametrize("name", list(REF_STEPS))
def test_ref_float64_inputs_keep_dtype_and_f32_accuracy(name):
    """Under x64, a float64 input must come back float64, with values at
    f32 accuracy (ref deliberately computes in f32 so the kernels and the
    oracle share an arithmetic contract across input dtypes)."""
    shape = (5, 7, 9) if name in NAMES_3D else (7, 9)
    x = _rand(shape, seed=42)
    with jax.enable_x64(True):
        xin = jnp.asarray(x, jnp.float64)
        assert xin.dtype == jnp.float64
        got = run_ref(name, xin, steps=1)
        assert got.dtype == jnp.float64
    np.testing.assert_allclose(
        np.asarray(got), _loop_run(name, x, steps=1), **TOL
    )


@pytest.mark.parametrize("name", list(REF_STEPS))
def test_ref_degenerate_interiors_are_identity(name):
    """Shapes with no interior (any extent <= 2) must pass through
    unchanged -- the Dirichlet border is the whole array."""
    shape = (2, 5, 2) if name in NAMES_3D else (2, 6)
    x = jnp.asarray(_rand(shape), jnp.float32)
    np.testing.assert_array_equal(np.asarray(run_ref(name, x)), np.asarray(x))


@pytest.mark.parametrize("name", ["jacobi2d", "gradient2d", "heat3d"])
def test_banded_pallas_kernels_close_the_triangle(name):
    """kernels -> ref -> scalar loops: the banded Pallas kernels must also
    match the scalar-loop truth directly (not only transitively), on odd
    shapes that stress their masking."""
    shape = (5, 3, 7) if name in NAMES_3D else (5, 7)
    x = _rand(shape, seed=9)
    got = stencil_step(name, jnp.asarray(x, jnp.float32), interpret=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float64), _loop_run(name, x, steps=1), **TOL
    )
