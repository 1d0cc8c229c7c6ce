"""Benchmark suite driver: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines (harness contract).

  bench_area                -- SIII.B-C  area calibration + validation
  bench_pareto              -- Fig. 3    design space + Pareto fronts
  bench_sweep               -- engine    NumPy vs compiled JAX sweep
  bench_sensitivity         -- Table II  per-stencil optimal architectures
  bench_cache_removal       -- SV.A      cache-less comparison
  bench_resource_allocation -- Fig. 4    area-fraction clustering
  bench_kernels             -- workload  Pallas stencil kernels vs oracle
  bench_measure             -- predict->measure->refit: tile-kernel grid +
                               machine-parameter calibration fit
  bench_meshopt             -- beyond-paper: TPU mesh codesign (eq. 18)
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
    "area", "pareto", "sweep", "sensitivity", "cache_removal",
    "resource_allocation", "kernels", "measure", "meshopt", "roofline",
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
    ap.add_argument(
        "--refine",
        action="store_true",
        help="sweep suite: add the batched coordinate-descent refine stage "
        "(speedup/quality delta lands in the artifact JSON)",
    )
    ap.add_argument(
        "--lm",
        action="store_true",
        help="sweep suite: also time the LM cell family (mesh-factorization "
        "sweep over the repo's model configs; docs/lm_codesign.md)",
    )
    args = ap.parse_args()
    if args.smoke:
        # env (not a global) so suite modules can check common.smoke()
        # regardless of import order
        os.environ["REPRO_BENCH_SMOKE"] = "1"
    if args.refine:
        os.environ["REPRO_BENCH_REFINE"] = "1"
    if args.lm:
        os.environ["REPRO_BENCH_LM"] = "1"
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()

    from . import (
        bench_area,
        bench_cache_removal,
        bench_kernels,
        bench_measure,
        bench_meshopt,
        bench_pareto,
        bench_portfolio,
        bench_resource_allocation,
        bench_roofline,
        bench_sensitivity,
        bench_service,
        bench_sweep,
    )

    suites = list(
        zip(
            SUITE_NAMES,
            [
                bench_area,
                bench_pareto,
                bench_sweep,
                bench_sensitivity,
                bench_cache_removal,
                bench_resource_allocation,
                bench_kernels,
                bench_measure,
                bench_meshopt,
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
            rec = mod.run()
        except Exception:  # noqa: BLE001
            failed.append(name)
            traceback.print_exc()
            continue
        if isinstance(rec, dict) and "suite" in rec:
            # repo-root perf trajectory: any suite returning a record dict
            # (currently sweep: per-engine wall times + device count) gets
            # a timestamped BENCH_<suite>.json entry, committed so
            # regressions are diffable across PRs.
            from .common import append_trajectory

            path = append_trajectory(rec["suite"], rec)
            print(f"# trajectory entry appended to {path}", file=sys.stderr)
    if failed:
        print(f"FAILED suites: {failed}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
