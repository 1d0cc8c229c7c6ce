"""Benchmark suite driver: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines (harness contract).

  bench_area                -- SIII.B-C  area calibration + validation
  bench_pareto              -- Fig. 3    design space + Pareto fronts
  bench_sensitivity         -- Table II  per-stencil optimal architectures
  bench_cache_removal       -- SV.A      cache-less comparison
  bench_resource_allocation -- Fig. 4    area-fraction clustering
  bench_kernels             -- workload  Pallas stencil kernels vs oracle
  bench_measure             -- predict->measure->refit: tile-kernel grid +
                               machine-parameter calibration fit
  bench_roofline            -- SRoofline summary from dry-run artifacts
  bench_service             -- query service: cold sweep vs warm artifact
  bench_portfolio           -- fleet codesign: K-design portfolio search,
                               NumPy oracle vs jitted JAX scorer

``--smoke`` runs every suite on tiny problem sizes / downsampled hardware
spaces (separate artifact cache), sized for a CI lane: the point is that
every code path executes, not that the numbers are publication-grade.
"""

from __future__ import annotations

import argparse
import os
import sys
import traceback


SUITE_NAMES = [
    "area", "pareto", "sensitivity", "cache_removal",
    "resource_allocation", "kernels", "measure", "roofline",
    "service", "portfolio",
]


def main() -> None:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument(
        "only", nargs="?", default=None, choices=SUITE_NAMES,
        help="run a single suite",
    )
    ap.add_argument(
        "--smoke",
        action="store_true",
        help="tiny CI-runnable sizes (downsampled hw space, small kernels)",
    )
    args = ap.parse_args()
    if args.smoke:
        # env (not a global) so suite modules can check common.smoke()
        # regardless of import order
        os.environ["REPRO_BENCH_SMOKE"] = "1"
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()

    from . import (
        bench_area,
        bench_cache_removal,
        bench_kernels,
        bench_measure,
        bench_pareto,
        bench_portfolio,
        bench_resource_allocation,
        bench_roofline,
        bench_sensitivity,
        bench_service,
    )

    suites = list(
        zip(
            SUITE_NAMES,
            [
                bench_area,
                bench_pareto,
                bench_sensitivity,
                bench_cache_removal,
                bench_resource_allocation,
                bench_kernels,
                bench_measure,
                bench_roofline,
                bench_service,
                bench_portfolio,
            ],
            strict=True,  # a skewed registry must be a hard error
        )
    )
    failed = []
    print("name,us_per_call,derived")
    for name, mod in suites:
        if args.only and args.only != name:
            continue
        try:
            mod.run()
        except Exception:  # noqa: BLE001
            failed.append(name)
            traceback.print_exc()
    if failed:
        print(f"FAILED suites: {failed}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
