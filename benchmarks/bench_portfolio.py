"""Fleet portfolio codesign (docs/portfolio.md): time the K-design subset
search over the paper workload's sweep, NumPy float64 oracle vs the jitted
JAX scorer, and check the two engines land on the same fleet objective."""

from __future__ import annotations

import time

import numpy as np

from repro.core import codesign, enumerate_hw_space
from repro.core.portfolio import optimize_portfolio, portfolio_candidates
from repro.core.workload import paper_workload

from .common import SMOKE_HW_STRIDE, emit, smoke

K = 2
BUDGET = 900.0  # mm^2 fleet budget, the docs' running example


def run() -> None:
    hw = enumerate_hw_space().downsample(SMOKE_HW_STRIDE if smoke() else 4)
    res = codesign(paper_workload(), hw=hw, engine="numpy")

    # the dominance prefilter is what makes C(n, K) enumerable: report how
    # hard it squeezes the swept space before any subset is scored
    n_cand = int(portfolio_candidates(
        np.asarray(res.hw.area, np.float64),
        np.asarray(res.cell_time, np.float64)).sum())

    t0 = time.perf_counter()
    p_np = optimize_portfolio(res, k=K, budget=BUDGET, objective="throughput")
    numpy_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    p_jax = optimize_portfolio(res, k=K, budget=BUDGET,
                               objective="throughput", engine="jax")
    jax_s = time.perf_counter() - t0

    # engines may name different members on a float32-level tie, but the
    # fleet objective itself must agree (tests/test_portfolio.py holds the
    # stronger bit-level contract; this is the perf lane's sanity check)
    rel = abs(p_jax.fleet_gflops - p_np.fleet_gflops) / p_np.fleet_gflops
    assert rel < 1e-5, (p_np.members, p_jax.members, rel)

    _, single = res.best(max_area=BUDGET)
    emit(
        f"portfolio_numpy_k{K}", numpy_s * 1e6,
        f"{len(hw)} hw -> {n_cand} candidates; fleet "
        f"{p_np.fleet_gflops:.0f} GFLOP/s @ {p_np.total_area:.0f} mm^2",
    )
    emit(
        f"portfolio_jax_k{K}", jax_s * 1e6,
        f"{numpy_s / jax_s:.1f}x vs numpy; members {list(p_jax.members)}",
    )
    emit(
        "portfolio_vs_single", numpy_s * 1e6,
        f"fleet {p_np.fleet_gflops:.0f} vs best single {single:.0f} GFLOP/s "
        f"under {BUDGET:.0f} mm^2",
    )
