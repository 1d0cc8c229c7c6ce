"""Reproduce Fig. 3 (design-space exploration + Pareto fronts) and the
§V.B workload-sensitivity analysis -- full 6-stencil workload.

Run: PYTHONPATH=src python examples/codesign_pareto.py [--fast] [--engine E]
(--fast subsamples the hardware space ~4x for a quicker demo; --engine
picks the eq.-18 inner solver: auto (default), jax, or numpy.)
"""

import argparse
import time

import numpy as np

from repro.core import GTX980, MAXWELL, TITAN_X, codesign, enumerate_hw_space
from repro.core.codesign import evaluate_fixed_hw
from repro.core.engines import ENGINES
from repro.core.pareto import pareto_mask
from repro.core.workload import paper_workload

ap = argparse.ArgumentParser()
ap.add_argument("--fast", action="store_true")
ap.add_argument("--engine", choices=ENGINES, default="auto")
args = ap.parse_args()

for cls, names in (
    ("2D", ["jacobi2d", "heat2d", "laplacian2d", "gradient2d"]),
    ("3D", ["heat3d", "laplacian3d"]),
):
    wl = paper_workload(names, name=f"paper-{cls}")
    hw = enumerate_hw_space(MAXWELL, max_area=650.0)
    if args.fast:
        hw = hw.downsample(4)
    t0 = time.perf_counter()
    res = codesign(wl, hw=hw, engine=args.engine)
    print(f"[{cls}] eq.-18 sweep ({args.engine}): {time.perf_counter()-t0:.1f}s")
    g = res.gflops()
    mask = pareto_mask(hw.area, g)
    print(f"\n=== {cls} stencils: {len(hw)} feasible designs ===")
    print(f"Pareto-optimal: {mask.sum()} ({100*mask.sum()/len(hw):.1f}%)")

    for name, point in (("GTX-980", GTX980), ("Titan X", TITAN_X)):
        _, stock = evaluate_fixed_hw(wl, point)
        a = MAXWELL.area_point(point)
        i, best = res.best(max_area=a)
        print(
            f"{name:8s} stock {stock:7.1f} GFLOP/s @ {a:.0f} mm^2 | "
            f"codesigned {best:7.1f} (+{100*(best/stock-1):.0f}%) "
            f"-> {res.hw.point(i)}"
        )

    # §V.B: per-stencil optima for free (re-weighting cached cell times)
    print("workload sensitivity (Table II analogue, 425-450 mm^2):")
    cells = list(wl.cells)
    for name in names:
        freqs = np.array(
            [1.0 / 16 if c.stencil.name == name else 0.0 for c in cells]
        )
        gs = res.gflops(freqs)
        gs = np.where((hw.area >= 425) & (hw.area <= 450), gs, -np.inf)
        i = int(np.argmax(gs))
        p = res.hw.point(i)
        print(
            f"  {name:12s} n_SM={p.n_sm:3d} n_V={p.n_v:4d} M_SM={p.m_sm:4.0f}kB "
            f"area={hw.area[i]:5.1f} {gs[i]:8.1f} GFLOP/s"
        )
