"""Stencil kernel microbenchmarks: Pallas (interpret) vs jnp oracle, with
useful-FLOP throughput. The kernels run in the Pallas interpreter by
explicit request, so the wall times are interpreter numbers on any host --
correctness + blocking behaviour is what is exercised here; the compiled
kernels are checked on the chip by ``chip_smoke.py``."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.ops import KERNELS, kernel_flops, stencil_run, tuned_block_rows
from repro.kernels.ref import run_ref

from .common import emit, smoke, timed

SHAPES = {2: (256, 256), 3: (32, 64, 64)}
SMOKE_SHAPES = {2: (64, 64), 3: (16, 32, 32)}
STEPS = 2


def run() -> None:
    shapes = SMOKE_SHAPES if smoke() else SHAPES
    for name, mod in KERNELS.items():
        shape = shapes[mod.DIMS]
        x = jax.random.normal(jax.random.PRNGKey(0), shape, jnp.float32)
        br = tuned_block_rows(name, shape, jnp.float32)

        run_k = lambda: stencil_run(
            name, x, steps=STEPS, block_rows=br, interpret=True
        ).block_until_ready()
        run_k()  # compile
        _, us_k = timed(run_k)

        run_r = lambda: jax.block_until_ready(run_ref(name, x, steps=STEPS))
        run_r()
        _, us_r = timed(run_r)

        got = stencil_run(name, x, steps=STEPS, block_rows=br, interpret=True)
        want = run_ref(name, x, steps=STEPS)
        err = float(jnp.abs(got - want).max())
        fl = kernel_flops(name, shape, STEPS)
        emit(
            f"kernel_{name}", us_k,
            f"blocks={br} rows, max|err|={err:.1e}, useful "
            f"{fl/us_k:.2f} MFLOP/s interp (jnp oracle {us_r:.0f} us)",
        )
        assert err < 1e-4
