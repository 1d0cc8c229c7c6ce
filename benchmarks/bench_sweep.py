"""NumPy chunked sweep vs compiled JAX sweep engines on the Fig.-3 workload.

Times the full eq.-(18) solve (every workload cell x every feasible
hardware point) once per engine -- NumPy oracle, single-device JAX, and
the shard_map multi-device engine -- and reports the wall-time ratios,
plus a cell-by-cell argmin equivalence check so the speedup is never
bought with a wrong answer (the sharded engine must be *bit-identical* to
the single-device one). Compiled numbers include compilation (cold
start); a warm second pass is reported separately to show the
steady-state gap. The per-engine wall times + device count land in the
repo-root ``BENCH_sweep.json`` trajectory via ``benchmarks/run.py``.

On a CPU host, ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
(set before jax initializes) exercises the real multi-device path; the
scaling-efficiency number is only meaningful when the forced devices map
to real cores.
"""

from __future__ import annotations

import time

import numpy as np

from repro.core import MAXWELL, codesign, enumerate_hw_space
from repro.core import sweep
from repro.core.workload import paper_workload

from .common import (
    SMOKE_HW_STRIDE,
    STENCIL_CLASSES as CLASSES,
    cache_json,
    emit,
    lm_enabled,
    refine_enabled,
    skey,
    smoke,
)


def _equivalent(res_np, res_jax) -> float:
    """Max relative gap between the engines' per-cell optima (the argmins
    may differ on exact ties; the achieved times must agree)."""
    finite = np.isfinite(res_np.cell_time)
    if not np.array_equal(finite, np.isfinite(res_jax.cell_time)):
        return float("inf")
    gap = np.abs(res_jax.cell_time[finite] - res_np.cell_time[finite])
    return float(np.max(gap / res_np.cell_time[finite]))


def _refine_stage(cls: str, res) -> dict:
    """Polish the reported best design with the batched coordinate descent
    (CodesignResult.refine) and land the speedup/quality delta in the
    artifact JSON -- the refine trajectory is now part of the tracked
    benchmark surface, not just a test fixture. The whole descent is one
    ``lax.while_loop`` dispatch (a single device->host sync), so refine_s
    here tracks the win over the old per-round blocking convergence check."""
    i, g0 = res.best(max_area=650.0)
    wt0 = float(res.weighted_time()[i])
    t0 = time.perf_counter()
    times, _ = res.refine(i)
    dt = time.perf_counter() - t0
    freqs = res.cell_freqs()
    wt1 = float(freqs @ times)
    flops = float(freqs @ res.cell_flops())
    g1 = flops / wt1 / 1.0e9
    improved = int(np.sum(times < res.cell_time[:, i]))
    rec = {
        "class": cls,
        "best_index": int(i),
        "refine_s": round(dt, 4),
        "cells_improved": improved,
        "cells": int(len(times)),
        "weighted_time_lattice_s": wt0,
        "weighted_time_refined_s": wt1,
        "gflops_lattice": g0,
        "gflops_refined": g1,
        "quality_delta_pct": 100.0 * (g1 / g0 - 1.0) if g0 else 0.0,
    }
    cache_json(skey(f"sweep_refine_{cls}"), lambda: rec, force=True)
    emit(
        f"sweep_refine_{cls}", dt * 1e6,
        f"best design {i}: {improved}/{len(times)} cells improved, "
        f"{g0:.1f} -> {g1:.1f} GFLOP/s ({rec['quality_delta_pct']:+.2f}%) "
        f"in {dt:.2f}s",
    )
    # wt0 is the jax engine's float32 sweep; wt1 is refine's float64
    # re-evaluation -- allow the cross-engine noise bound (same RTOL as the
    # equivalence tests), not a bitwise comparison
    assert wt1 <= wt0 * (1 + 1e-5), "refine regressed the lattice optimum"
    return rec


def _lm_stage() -> dict:
    """Time the LM cell family's eq.-(18) sweep (mesh factorizations x
    parallelism plans; see docs/lm_codesign.md) on both engines and check
    they agree -- feasibility bit-equal, achieved times within float32
    noise. The LM lattice is tiny next to a stencil sweep, so this stage
    reports the sweep *and* the warm re-dispatch cost, smoke or not; smoke
    shrinks the models (``cfg.reduced()``) and the chip budget so the
    ``jax.eval_shape`` parameter counting stays CI-cheap."""
    from repro.configs import get_arch
    from repro.core.lmcells import lm_codesign, lm_workload

    names = ["llama3-8b", "mixtral-8x22b"]
    if smoke():
        archs = [get_arch(n).reduced() for n in names]
        max_chips = 64
    else:
        archs = list(names)
        max_chips = 512
    wl = lm_workload(archs=archs, name="bench-lm")

    t0 = time.perf_counter()
    res_np = lm_codesign(wl, max_chips=max_chips, engine="numpy")
    t_np = time.perf_counter() - t0

    rec = {
        "models": names,
        "smoke_reduced": smoke(),
        "cells": len(wl.cells),
        "hw_points": len(res_np.hw),
        "max_chips": max_chips,
        "numpy_s": round(t_np, 4),
    }
    derived = f"{len(wl.cells)} cells x {len(res_np.hw)} meshes: numpy {t_np:.2f}s"
    if sweep.HAVE_JAX:
        t0 = time.perf_counter()
        res_jax = lm_codesign(wl, max_chips=max_chips, engine="jax")
        t_cold = time.perf_counter() - t0
        t0 = time.perf_counter()
        lm_codesign(wl, max_chips=max_chips, engine="jax")
        t_warm = time.perf_counter() - t0

        finite = np.isfinite(res_np.cell_time)
        assert np.array_equal(finite, np.isfinite(res_jax.cell_time)), (
            "LM engines disagree on feasibility"
        )
        gap = float(np.max(np.abs(
            res_jax.cell_time[finite] / res_np.cell_time[finite] - 1.0
        ))) if finite.any() else 0.0
        # jax runs the grid in float32; the oracle is float64 -- the tests
        # (tests/test_lmcells.py) pin the tie-aware argmin contract, the
        # bench just refuses to report a speedup bought with a wrong answer
        assert gap < 1e-4, f"LM engines diverged: {gap}"
        rec.update(
            jax_cold_s=round(t_cold, 4), jax_warm_s=round(t_warm, 4),
            max_rel_gap=gap,
        )
        derived += (
            f", jax cold {t_cold:.2f}s / warm {t_warm:.3f}s; "
            f"max rel gap {gap:.1e}"
        )
    else:
        derived += " (jax not installed; oracle only)"
    cache_json(skey("sweep_lm"), lambda: rec, force=True)
    emit("sweep_lm", t_np * 1e6, derived)
    return rec


def run() -> dict | None:
    """Run the engine comparison; returns the trajectory record that
    ``benchmarks/run.py`` appends to the repo-root ``BENCH_sweep.json``."""
    if not sweep.HAVE_JAX:
        emit("sweep_engine", 0.0, "skipped (jax not installed)")
        return None
    n_dev = sweep.device_count()
    # the 1-device mesh is the degenerate case (same program as "jax", and
    # tests/test_sweep_sharded.py already pins its bit-identity): timing it
    # would double the compiled-engine cost of the single-device smoke lane
    # for no signal. The CI sharded lane forces 8 host devices.
    run_sharded = n_dev > 1
    hw = enumerate_hw_space(MAXWELL, max_area=650.0)
    if smoke():
        hw = hw.downsample(SMOKE_HW_STRIDE)
    totals = {"numpy": 0.0, "jax_cold": 0.0, "jax_warm": 0.0,
              "sharded_cold": 0.0, "sharded_warm": 0.0}
    classes: dict = {}
    for cls, names in CLASSES.items():
        wl = paper_workload(names, name=f"sweep-{cls}")
        sweep.clear_caches()  # honest cold start: compile time is charged

        t0 = time.perf_counter()
        res_jax = codesign(wl, hw=hw, engine="jax")
        t_cold = time.perf_counter() - t0

        t0 = time.perf_counter()
        codesign(wl, hw=hw, engine="jax")
        t_warm = time.perf_counter() - t0

        if run_sharded:
            t0 = time.perf_counter()
            res_sh = codesign(wl, hw=hw, engine="sharded")
            t_sh_cold = time.perf_counter() - t0

            t0 = time.perf_counter()
            codesign(wl, hw=hw, engine="sharded")
            t_sh_warm = time.perf_counter() - t0

        t0 = time.perf_counter()
        res_np = codesign(wl, hw=hw, engine="numpy")
        t_np = time.perf_counter() - t0

        gap = _equivalent(res_np, res_jax)
        assert gap < 1e-5, f"engines diverged on {cls}: {gap}"
        totals["numpy"] += t_np
        totals["jax_cold"] += t_cold
        totals["jax_warm"] += t_warm
        classes[cls] = {
            "cells": len(wl.cells), "hw": len(hw), "numpy_s": round(t_np, 4),
            "jax_cold_s": round(t_cold, 4), "jax_warm_s": round(t_warm, 4),
        }
        emit(
            f"sweep_{cls}", t_cold * 1e6,
            f"{len(wl.cells)} cells x {len(hw)} hw: numpy {t_np:.1f}s, "
            f"jax cold {t_cold:.1f}s ({t_np/t_cold:.1f}x) / warm {t_warm:.1f}s "
            f"({t_np/t_warm:.1f}x); max argmin gap {gap:.1e}",
        )
        if run_sharded:
            # the sharded engine runs the same compiled body per shard: any
            # difference from the single-device engine is a sharding bug,
            # so the bar is bit-identity, not a tolerance.
            assert np.array_equal(res_sh.cell_time, res_jax.cell_time) and (
                np.array_equal(res_sh.cell_tile_idx, res_jax.cell_tile_idx)
            ), f"sharded engine not bit-identical on {cls}"
            totals["sharded_cold"] += t_sh_cold
            totals["sharded_warm"] += t_sh_warm
            classes[cls]["sharded_cold_s"] = round(t_sh_cold, 4)
            classes[cls]["sharded_warm_s"] = round(t_sh_warm, 4)
            emit(
                f"sweep_sharded_{cls}", t_sh_cold * 1e6,
                f"{n_dev} device(s): cold {t_sh_cold:.1f}s / warm "
                f"{t_sh_warm:.1f}s ({t_warm/t_sh_warm:.2f}x vs single-device "
                f"warm); bit-identical",
            )
        if refine_enabled():
            r = _refine_stage(cls, res_jax)
            classes[cls]["refine_s"] = r["refine_s"]
            classes[cls]["refine_quality_delta_pct"] = round(
                r["quality_delta_pct"], 4
            )
    emit(
        "sweep_total", totals["jax_cold"] * 1e6,
        f"numpy {totals['numpy']:.1f}s vs jax {totals['jax_cold']:.1f}s cold "
        f"incl. compile -> {totals['numpy']/totals['jax_cold']:.1f}x",
    )
    if not run_sharded:
        for k in ("sharded_cold", "sharded_warm"):
            del totals[k]  # never timed; zeros would read as measurements
    rec = {
        "suite": "sweep",
        "smoke": smoke(),
        "device_count": n_dev,
        "hw_points": len(hw),
        "classes": classes,
        "engines_total_s": {k: round(v, 4) for k, v in totals.items()},
    }
    if lm_enabled():
        rec["lm"] = _lm_stage()
    if run_sharded:
        # scaling efficiency: warm speedup over the single-device engine
        # per mesh device. 1.0 = perfect linear scaling; meaningful only
        # when the devices are real (forced host devices share cores).
        speedup = totals["jax_warm"] / max(totals["sharded_warm"], 1e-9)
        efficiency = speedup / max(n_dev, 1)
        emit(
            "sweep_sharded_total", totals["sharded_cold"] * 1e6,
            f"{n_dev} device(s): warm {totals['sharded_warm']:.1f}s vs "
            f"single-device warm {totals['jax_warm']:.1f}s -> {speedup:.2f}x "
            f"({100 * efficiency:.0f}% scaling efficiency)",
        )
        rec["sharded_speedup_vs_jax_warm"] = round(speedup, 4)
        rec["scaling_efficiency"] = round(efficiency, 4)
    else:
        emit(
            "sweep_sharded_total", 0.0,
            f"skipped ({n_dev} device(s); needs a multi-device mesh "
            "-- see the CI sharded-smoke lane)",
        )
    return rec
