"""Compile the main path for a described TPU v5e, with no chip attached.

The TPU compiler is installed beside JAX, and it compiles for a topology
that is described rather than attached: what it refuses here (a block
shape Mosaic cannot tile, a kernel over its VMEM budget, a sharding it
cannot partition) the chip would refuse too. Nothing runs, so these tests
say nothing about results or times.

Only one process may load the TPU library at a time, so the topology is
described inside a module-scoped fixture, never while a module is
imported, and every test that needs it lives in this one file.
"""

import functools
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.core import sweep
from repro.core.solver import LATTICE_2D, LATTICE_3D
from repro.core.timemodel import MAXWELL_GPU
from repro.kernels import ops
from repro.kernels.pallas_stencils import run_tiled
from repro.measure.harness import default_grid

#: the paper sweep's shape: 16 problem sizes x the section IV.B space
PAPER_P, PAPER_H = 16, 5121


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these tests
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe means skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was_enabled)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _largest_shapes():
    """stencil -> the largest shape of the full measurement grid."""
    grid = default_grid(smoke=False)
    return {
        name: max((tuple(c["shape"]) for c in cfgs), key=np.prod)
        for name, cfgs in grid.items()
    }


@pytest.mark.parametrize("name", sorted(_largest_shapes()))
def test_tiled_kernel_compiles_at_full_grid_size(name, one_chip):
    shape = _largest_shapes()[name]
    x = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    compiled = jax.jit(
        lambda v: run_tiled(name, v, steps=8, tiles=None, interpret=False)
    ).lower(x).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize(
    "name,shape", [("jacobi2d", (1024, 1024)), ("heat3d", (64, 64, 128))]
)
def test_banded_kernel_compiles(name, shape, one_chip):
    x = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    compiled = jax.jit(
        lambda v: ops.stencil_step(name, v, interpret=False)
    ).lower(x).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _solver_args(h, sharding, hw_sharding=None):
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32)
    hw = hw_sharding or sharding
    return (
        f32((h,), sharding=hw), f32((h,), sharding=hw), f32((h,), sharding=hw),
        f32((PAPER_P + 1, 4), sharding=sharding),  # the sizes, then the stencil's scalars
    )


@pytest.mark.parametrize("dims,lattice", [(2, LATTICE_2D), (3, LATTICE_3D)])
def test_sweep_solver_compiles_at_paper_size(dims, lattice, one_chip):
    chunk = sweep.DEFAULT_CHUNK // PAPER_P
    solve = sweep._cells_solver(dims, MAXWELL_GPU, lattice, chunk)
    compiled = solve.lower(*_solver_args(PAPER_H, one_chip)).compile()
    assert compiled.memory_analysis() is not None


def test_sharded_solver_compiles_over_four_chips(topo):
    devices = tuple(topo.devices[:4])
    # the padding sweep_cells_sharded applies: whole chunks on every device
    chunk = min(sweep.DEFAULT_CHUNK // PAPER_P, -(-PAPER_H // len(devices)))
    quantum = len(devices) * chunk
    h_pad = -(-PAPER_H // quantum) * quantum
    mesh, solve = sweep._sharded_cells_solver(
        2, MAXWELL_GPU, LATTICE_2D, chunk, devices
    )
    assert isinstance(mesh, Mesh) and mesh.devices.size == 4
    args = _solver_args(
        h_pad,
        NamedSharding(mesh, PartitionSpec()),
        NamedSharding(mesh, PartitionSpec("hw")),
    )
    compiled = solve.lower(*args).compile()
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize("op", ["prefill", "decode", "train", "moe_dispatch"])
def test_lm_grid_compiles_at_deepseek_size(op, one_chip):
    """DeepSeek-V3's question: one grid per op over the 144 meshes of up
    to 2,048 chips and the op's software lattice."""
    from repro.configs.base import SHAPES
    from repro.core.lmcells import (LMCell, _cell_consts, _jax_grid_fn, enumerate_lm_hw_space,
                                    lm_sw_lattice)

    h, l = len(enumerate_lm_hw_space(max_chips=2048)), len(lm_sw_lattice(op))
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32, sharding=one_chip)
    cell = LMCell(model="m", op=op, shape=SHAPES["decode_32k"], freq=1.0, n_params=0,
                  n_active=0, kv_bytes=0, d_model=0, n_layers=0, flops=0.0)
    compiled = _jax_grid_fn(op).lower(f32((len(_cell_consts(cell)),)), f32((3, h))).compile()
    assert compiled.memory_analysis() is not None
    assert compiled.out_info.shape == (h, l)
