"""End-to-end driver: codesign -> configure kernels -> execute -> report.

The full loop the paper envisions, on this machine:
1. solve the codesign problem for a Jacobi-2D workload (analytic),
2. take the winning *software* parameters (the tile sizes),
3. map them onto the TPU Pallas kernel's block plan (DESIGN.md: the VMEM
   feasibility constraint is the eq. 9/11 analogue),
4. execute the Pallas kernel -- compiled on a TPU, in the Pallas
   interpreter with ``--interpret`` -- against the jnp oracle and report
   correctness + achieved useful FLOP/s.

Run: PYTHONPATH=src python examples/stencil_codesign_e2e.py [--interpret]
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import MAXWELL_GPU, STENCILS, ProblemSize, solve_cell
from repro.core.solver import LATTICE_2D, decode_index
from repro.kernels.ops import kernel_flops, stencil_run, tuned_block_rows
from repro.kernels.ref import run_ref

ap = argparse.ArgumentParser(description=__doc__)
ap.add_argument("--interpret", action="store_true",
                help="run the kernel in the Pallas interpreter (no TPU)")
args = ap.parse_args()

# --- 1. codesign: optimal tiles for a 2048^2 x 64 Jacobi-2D cell ----------
spec = STENCILS["jacobi2d"]
size = ProblemSize(2048, 2048, 64)
hw = (np.array([16.0]), np.array([128.0]), np.array([96.0]))  # GTX-980 point
t, idx = solve_cell(spec, MAXWELL_GPU, size, *hw, LATTICE_2D)
tiles = decode_index(LATTICE_2D, int(idx[0]))
print(f"analytic optimum: T_alg={t[0]*1e3:.1f} ms, tiles={tiles}")

# --- 2-3. map the software solve onto the TPU kernel's block plan ---------
shape = (512, 512)
steps = 8
x = jax.random.normal(jax.random.PRNGKey(0), shape, jnp.float32)
block_rows = tuned_block_rows("jacobi2d", shape, jnp.float32)
print(f"TPU block plan: band of {block_rows} rows (VMEM-fit solve)")

# --- 4. execute + validate -------------------------------------------------
t0 = time.perf_counter()
got = stencil_run(
    "jacobi2d", x, steps=steps, block_rows=block_rows, interpret=args.interpret
)
got.block_until_ready()
dt = time.perf_counter() - t0
want = run_ref("jacobi2d", x, steps=steps)
err = float(jnp.abs(got - want).max())
flops = kernel_flops("jacobi2d", shape, steps)
print(
    f"ran {steps} steps of {shape} in {dt*1e3:.0f} ms "
    f"(interpret={args.interpret}): max|err| = {err:.2e}, useful {flops/dt/1e6:.1f} MFLOP/s"
)
assert err < 1e-5
print("OK: Pallas kernel matches the oracle with codesigned blocks")
