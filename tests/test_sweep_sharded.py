"""Sharded (shard_map) sweep engine vs the single-device JAX engine.

The sharded engine runs the *same* fused time-model body per shard, so the
bar is **bit-identity** with :func:`repro.core.sweep.sweep_cells` -- not a
tolerance -- for every padding regime (H not divisible by devices x chunk,
H smaller than the device count) and every `devices=` selection of
:func:`repro.core.sweep.sweep_cells_sharded`. Through ``codesign()`` the
mesh is reached only by ``engine="auto"`` with more than one device
attached; where one is, the tests monkeypatch ``sweep.device_count``. The
CI sharded lane runs this file under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so the mesh is a
real 8-way partition; on a plain host the same tests exercise the 1-device
mesh (the degenerate but still shard_map-compiled path), and a subprocess
test forces the 8-device view regardless.
"""

import subprocess
import sys

import numpy as np
import pytest

from repro.core import MAXWELL, MAXWELL_GPU, STENCILS, codesign, enumerate_hw_space
from repro.core import sweep
from repro.core.engines import dispatch_engine
from repro.core.solver import LATTICE_2D
from repro.core.workload import paper_workload


def small_hw(step=16):
    return enumerate_hw_space(MAXWELL, max_area=650.0).downsample(step)


def hw_cols(hw):
    return hw.n_sm, hw.n_v, hw.m_sm


SIZES_2D = np.array([[4096, 4096, 1, 1024], [2048, 2048, 1, 512]], np.float64)


@pytest.fixture
def many_devices(monkeypatch):
    """engine="auto" sees a many-device host: codesign() takes the mesh
    engine, over every device actually attached."""
    monkeypatch.setattr(sweep, "device_count", lambda: 8)


def test_sharded_bit_identical_paper_sweep(many_devices):
    """Full six-stencil paper workload: the sharded driver path must equal
    the single-device engine bit-for-bit (times AND argmin indices)."""
    wl = paper_workload()
    hw = small_hw(step=24)
    assert dispatch_engine("auto", len(hw)) == "sharded"
    res_jax = codesign(wl, hw=hw, engine="jax")
    res_sh = codesign(wl, hw=hw, engine="auto")
    np.testing.assert_array_equal(res_sh.cell_time, res_jax.cell_time)
    np.testing.assert_array_equal(res_sh.cell_tile_idx, res_jax.cell_tile_idx)


@pytest.mark.parametrize("chunk", [None, 0, 7, 64])
def test_sharded_padding_is_invisible(chunk):
    """H deliberately not divisible by devices x chunk: the pad rows must
    never leak into results, for chunked and unchunked shard programs."""
    st = STENCILS["jacobi2d"]
    hw = small_hw(step=13)  # 394 points: not a multiple of 8 x any chunk
    t_ref, i_ref = sweep.sweep_cells(
        st, MAXWELL_GPU, SIZES_2D, *hw_cols(hw), LATTICE_2D, chunk
    )
    t, i = sweep.sweep_cells_sharded(
        st, MAXWELL_GPU, SIZES_2D, *hw_cols(hw), LATTICE_2D, chunk
    )
    np.testing.assert_array_equal(t, t_ref)
    np.testing.assert_array_equal(i, i_ref)


@pytest.mark.parametrize("n_hw", [1, 3, 7])
def test_sharded_tiny_hardware_spaces(n_hw):
    """H < devices (under the CI 8-device lane) and H < chunk: every
    device still gets a full-shaped shard via padding; results drop it."""
    st = STENCILS["jacobi2d"]
    hw = small_hw(step=16)
    cols = tuple(c[:n_hw] for c in hw_cols(hw))
    t_ref, i_ref = sweep.sweep_cells(
        st, MAXWELL_GPU, SIZES_2D, *cols, LATTICE_2D, 5
    )
    t, i = sweep.sweep_cells_sharded(
        st, MAXWELL_GPU, SIZES_2D, *cols, LATTICE_2D, 5
    )
    assert t.shape == (SIZES_2D.shape[0], n_hw)
    np.testing.assert_array_equal(t, t_ref)
    np.testing.assert_array_equal(i, i_ref)


def test_sharded_empty_hardware_space():
    st = STENCILS["jacobi2d"]
    empty = np.empty(0)
    t, i = sweep.sweep_cells_sharded(
        st, MAXWELL_GPU, SIZES_2D, empty, empty, empty, LATTICE_2D
    )
    assert t.shape == (2, 0) and i.shape == (2, 0)


def test_sharded_devices_knob():
    """devices= as an int prefix and as an explicit device list must agree
    with the all-devices default; out-of-range counts are rejected."""
    import jax

    st = STENCILS["jacobi2d"]
    hw = small_hw(step=16)
    t_ref, i_ref = sweep.sweep_cells_sharded(
        st, MAXWELL_GPU, SIZES_2D, *hw_cols(hw), LATTICE_2D
    )
    for devices in (1, len(jax.devices()), list(jax.devices())):
        t, i = sweep.sweep_cells_sharded(
            st, MAXWELL_GPU, SIZES_2D, *hw_cols(hw), LATTICE_2D, devices=devices
        )
        np.testing.assert_array_equal(t, t_ref)
        np.testing.assert_array_equal(i, i_ref)
    with pytest.raises(ValueError, match="out of range"):
        sweep.sweep_cells_sharded(
            st, MAXWELL_GPU, SIZES_2D, *hw_cols(hw), LATTICE_2D,
            devices=len(jax.devices()) + 1,
        )


def test_engine_auto_promotes_on_multi_device(monkeypatch):
    """auto -> sharded iff >1 device; -> jax on one device; -> numpy below
    the compile-amortization floor, whatever the device count."""
    monkeypatch.setattr(sweep, "device_count", lambda: 8)
    assert dispatch_engine("auto", 1000) == "sharded"
    assert dispatch_engine("auto", 3) == "numpy"  # tiny space: no compile
    monkeypatch.setattr(sweep, "device_count", lambda: 1)
    assert dispatch_engine("auto", 1000) == "jax"
    assert dispatch_engine("auto", 3) == "numpy"


def test_devices_knob_implies_mesh_engine(monkeypatch):
    """The device count alone decides the mesh, and only under auto: an
    explicit engine stays what it names on a many-device host, and the
    driver takes no devices= of its own."""
    monkeypatch.setattr(sweep, "device_count", lambda: 8)
    assert dispatch_engine("jax", 1000) == "jax"
    assert dispatch_engine("jax", 3) == "jax"  # the floor binds auto only
    assert dispatch_engine("numpy", 1000) == "numpy"
    wl = paper_workload(["jacobi2d"])
    res_auto = codesign(wl, hw=small_hw(step=64), engine="auto")
    res_jax = codesign(wl, hw=small_hw(step=64), engine="jax")
    np.testing.assert_array_equal(res_auto.cell_time, res_jax.cell_time)
    np.testing.assert_array_equal(res_auto.cell_tile_idx, res_jax.cell_tile_idx)


def test_sharded_matches_numpy_oracle_reductions(many_devices):
    """Workload-level reductions through the full driver stack agree with
    the float64 oracle within the cross-engine noise bound."""
    wl = paper_workload(["heat2d", "heat3d"], name="sharded-parity")
    hw = small_hw(step=48)
    res_np = codesign(wl, hw=hw, engine="numpy")
    res_sh = codesign(wl, hw=hw, engine="auto")
    np.testing.assert_allclose(
        res_sh.weighted_time(), res_np.weighted_time(), rtol=1e-5
    )
    np.testing.assert_allclose(res_sh.gflops(), res_np.gflops(), rtol=1e-5)


_FORCED_8DEV_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax
assert jax.device_count() == 8, jax.device_count()
from repro.core import MAXWELL, codesign, enumerate_hw_space
from repro.core.engines import dispatch_engine
from repro.core.workload import paper_workload

assert dispatch_engine("auto", 1000) == "sharded"
wl = paper_workload(["jacobi2d", "heat3d"], name="forced8")
hw = enumerate_hw_space(MAXWELL, max_area=650.0).downsample(32)
res_jax = codesign(wl, hw=hw, engine="jax")
res_sh = codesign(wl, hw=hw, engine="auto")
assert np.array_equal(res_sh.cell_time, res_jax.cell_time)
assert np.array_equal(res_sh.cell_tile_idx, res_jax.cell_tile_idx)
print("FORCED8_OK")
"""


@pytest.mark.slow
def test_sharded_bit_identical_under_forced_8_devices(subprocess_env):
    """End-to-end 8-way mesh regardless of the host: a subprocess forces
    the host-device count before jax initializes (XLA locks devices at
    import, so this cannot be tested in-process once jax is loaded)."""
    env = subprocess_env
    env.pop("XLA_FLAGS", None)  # the script sets its own device count
    out = subprocess.run(
        [sys.executable, "-c", _FORCED_8DEV_SCRIPT],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, out.stderr
    assert "FORCED8_OK" in out.stdout
