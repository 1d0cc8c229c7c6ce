"""Measurement + calibration loop: time the tile-parameterized Pallas
stencils over a grid, refit the time model's machine parameters from the
timings, and land the per-stencil predicted-vs-measured error
before/after refit in a JSON artifact. A synthetic-recovery stage asserts the fit itself is sound
(model-generated timings from perturbed starting parameters must recover
the generating machine) -- the empirical-loop analogue of the sweep
suite's engine-parity asserts."""

from __future__ import annotations

import time

from repro.core.timemodel import MAXWELL_GPU, STENCILS, with_c_iter, with_machine_params
from repro.measure import fit_machine_params, measure_grid, synthetic_records
from repro.measure.calibrate import RECOVERY_RTOL
from repro.measure.harness import default_grid

from .common import cache_json, emit, skey, smoke


def run() -> None:
    # --- stage 1: the measurement grid (Pallas kernels, interpret mode: the
    # compiled kernels are timed on the chip by `measure.cli run`) ---------
    grid = default_grid(smoke=smoke())
    n_cfg = sum(len(v) for v in grid.values())
    t0 = time.perf_counter()
    measured = measure_grid(grid, warmup=1, repeats=2, interpret=True)
    t_grid = time.perf_counter() - t0
    emit(
        "measure_grid", t_grid / n_cfg * 1e6,
        f"{len(measured.records)} records / {n_cfg} configs in {t_grid:.1f}s "
        f"(backend={measured.backend}, interpret={measured.interpret})",
    )

    # --- stage 2: refit machine parameters from the harness timings -------
    t0 = time.perf_counter()
    cal = fit_machine_params(measured, iters=600 if smoke() else 1500)
    t_fit = time.perf_counter() - t0
    mean_before = sum(cal.errors_before.values()) / len(cal.errors_before)
    mean_after = sum(cal.errors_after.values()) / len(cal.errors_after)
    emit(
        "measure_fit", t_fit * 1e6,
        f"log-space loss {cal.loss_before:.3g} -> {cal.loss_after:.3g}; "
        f"mean |rel err| {mean_before:.1%} -> {mean_after:.1%} "
        f"over {cal.n_records} records",
    )
    assert cal.loss_after < cal.loss_before, "refit must reduce the fit loss"
    cache_json(
        skey("measure_calibration"),
        lambda: {
            "records": len(measured.records),
            "backend": measured.backend,
            "interpret": measured.interpret,
            "calibration": cal.to_payload(),
        },
        force=True,
    )

    # --- stage 3: synthetic recovery (the fit's own acceptance check) -----
    truth_gpu = with_machine_params(
        MAXWELL_GPU, bw_gmem=150.0e9, launch_overhead=8.0e-6
    )
    truth_st = {
        n: with_c_iter(st, st.c_iter * (1.0 + 0.25 * (i + 1)))
        for i, (n, st) in enumerate(STENCILS.items())
    }
    t0 = time.perf_counter()
    rec = fit_machine_params(
        synthetic_records(truth_gpu, truth_st), gpu0=MAXWELL_GPU
    )
    t_syn = time.perf_counter() - t0
    err = rec.param_rel_error(truth_gpu, truth_st)
    emit(
        "measure_synthetic_recovery", t_syn * 1e6,
        f"max param rel err {err:.2e} (acceptance < {RECOVERY_RTOL})",
    )
    assert err < RECOVERY_RTOL, f"synthetic recovery off by {err:.1%}"
