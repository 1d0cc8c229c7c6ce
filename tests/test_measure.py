"""repro.measure: harness record discipline, payload round trips, the
synthetic-recovery acceptance property (fitting model-generated timings
from perturbed starting parameters recovers the generating machine), and
the measurement/calibration artifact kinds in the store."""

import numpy as np
import pytest

from repro.core.timemodel import (
    MAXWELL_GPU,
    STENCILS,
    with_c_iter,
    with_machine_params,
)
from repro.measure import (
    CalibrationResult,
    MeasurementRecord,
    MeasurementRun,
    fit_machine_params,
    measure_one,
    predicted_times,
    synthetic_records,
)
from repro.measure.harness import STOCK_HW, feasible_tiles


def _truth():
    """A 'real machine' deliberately off the datasheet on every parameter."""
    gpu = with_machine_params(MAXWELL_GPU, bw_gmem=150.0e9, launch_overhead=8.0e-6)
    sts = {
        n: with_c_iter(st, st.c_iter * (1.0 + 0.25 * (i + 1)))
        for i, (n, st) in enumerate(STENCILS.items())
    }
    return gpu, sts


# ---------------------------------------------------------------------------
# harness
# ---------------------------------------------------------------------------
def test_measure_one_record_contract():
    rec = measure_one(
        "heat2d", (24, 40), steps=4, tiles={"t_s1": 8, "t_s2": 32, "t_t": 2},
        warmup=1, repeats=2, interpret=True,
    )
    assert rec.stencil == "heat2d"
    assert rec.size == (24, 40, 1, 4)
    # 2D records are framed at t_s3=1 (the kernel never reads t_s3 in 2D,
    # and the model's compute term multiplies by it)
    assert rec.tiles == (8, 32, 2, 1, 1)
    assert rec.time_s > 0
    assert rec.hw == (STOCK_HW["n_sm"], STOCK_HW["n_v"], STOCK_HW["m_sm"])
    # JSON round trip is lossless
    assert MeasurementRecord.from_json(rec.to_json()) == rec


def test_measurement_run_payload_round_trip():
    rec = MeasurementRecord(
        stencil="jacobi2d", size=(64, 64, 1, 4), tiles=(8, 32, 2, 1, 1),
        time_s=1.25e-3, hw=(16.0, 128.0, 96.0),
    )
    run = MeasurementRun(
        records=[rec], gpu_name="gtx980", backend="cpu", interpret=True, note="x"
    )
    back = MeasurementRun.from_payload(run.to_payload())
    assert back.records == run.records
    assert (back.gpu_name, back.backend, back.interpret, back.note) == (
        "gtx980", "cpu", True, "x",
    )
    assert back.stencil_names() == ["jacobi2d"]
    # runs recorded before the device fields existed still load
    assert (back.device_kind, back.device_count) == (None, None)
    legacy = run.to_payload()
    del legacy["device_kind"], legacy["device_count"]
    assert MeasurementRun.from_payload(legacy).records == run.records


def test_measurement_run_names_its_device():
    from repro.measure.harness import measure_grid

    run = measure_grid(
        {"jacobi2d": [{"shape": (16, 24), "steps": 2,
                       "tiles": {"t_s1": 8, "t_s2": 128, "t_t": 2}}]},
        warmup=0, repeats=1, interpret=True,
    )
    import jax

    assert run.device_kind == jax.devices()[0].device_kind
    assert run.device_count == jax.device_count()
    back = MeasurementRun.from_payload(run.to_payload())
    assert (back.device_kind, back.device_count) == (
        run.device_kind, run.device_count,
    )


def test_measure_cli_run_without_chip_exits_2(tmp_path, monkeypatch, capsys):
    """No TPU and no --interpret: one line, exit 2, nothing measured."""
    import jax

    from repro.measure import cli

    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    monkeypatch.setattr(cli, "enable_compile_cache", lambda: None)
    with pytest.raises(SystemExit) as exc:
        cli.main(["run", "--store", str(tmp_path)])
    assert exc.value.code == 2
    err = capsys.readouterr().err.strip().splitlines()
    assert len(err) == 1 and "--interpret" in err[0]
    assert not any(tmp_path.iterdir())


def test_feasible_tiles_filters_model_infeasible():
    cands = [
        {"t_s1": 8, "t_s2": 32, "t_t": 2, "k": 1},  # fine
        {"t_s1": 8, "t_s2": 33, "t_t": 2, "k": 1},  # violates warp multiple
        {"t_s1": 8, "t_s2": 32, "t_t": 3, "k": 1},  # violates even t_T
        {"t_s1": 512, "t_s2": 1024, "t_t": 64, "k": 32},  # footprint blowout
    ]
    kept = feasible_tiles("heat2d", cands)
    assert kept == [{"t_s1": 8, "t_s2": 32, "t_t": 2, "k": 1, "t_s3": 1}]
    # 2D candidates differing only in t_s3 collapse to one framed config
    dup = feasible_tiles(
        "heat2d",
        [{"t_s1": 8, "t_s2": 32, "t_t": 2, "k": 1, "t_s3": 8},
         {"t_s1": 8, "t_s2": 32, "t_t": 2, "k": 1, "t_s3": 4}],
    )
    assert len(dup) == 1
    # 3D keeps distinct t_s3 values distinct
    dup3 = feasible_tiles(
        "heat3d",
        [{"t_s1": 4, "t_s2": 32, "t_t": 2, "k": 1, "t_s3": 8},
         {"t_s1": 4, "t_s2": 32, "t_t": 2, "k": 1, "t_s3": 4}],
    )
    assert len(dup3) == 2


def test_stock_hw_follows_gpu_family():
    """A titanx-framed run must be stamped (and feasibility-filtered) at
    the Titan X's stock hardware point, not the GTX-980's."""
    from repro.core.timemodel import TITANX_GPU
    from repro.measure.harness import measure_grid, stock_hw

    assert stock_hw(TITANX_GPU)["n_sm"] == 24.0
    assert stock_hw(MAXWELL_GPU)["n_sm"] == 16.0
    run = measure_grid(
        {"heat2d": [{"shape": (32, 48), "steps": 2,
                     "tiles": {"t_s1": 8, "t_s2": 32, "t_t": 2, "t_s3": 1}}]},
        warmup=0, repeats=1, interpret=True, gpu=TITANX_GPU,
    )
    assert run.records[0].hw == (24.0, 128.0, 96.0)
    assert run.gpu_name == "titanx"


# ---------------------------------------------------------------------------
# calibration
# ---------------------------------------------------------------------------
def test_predicted_times_match_model_and_flag_infeasible():
    recs = synthetic_records(MAXWELL_GPU)
    pred = predicted_times(recs, MAXWELL_GPU)
    np.testing.assert_allclose(pred, [r.time_s for r in recs], rtol=1e-12)
    bad = MeasurementRecord(
        stencil="heat2d", size=(64, 64, 1, 4), tiles=(8, 33, 2, 1, 1),
        time_s=1.0, hw=(16.0, 128.0, 96.0),
    )
    assert not np.isfinite(predicted_times([bad], MAXWELL_GPU)[0])


def test_synthetic_fit_recovers_generating_parameters():
    """The CI acceptance property: exact model-generated timings, fit
    started from the (wrong) datasheet parameters, must land back on the
    generating machine to sub-percent relative error."""
    gpu_t, st_t = _truth()
    recs = synthetic_records(gpu_t, st_t)
    cal = fit_machine_params(recs, gpu0=MAXWELL_GPU, stencils0=STENCILS)
    assert cal.n_dropped == 0
    assert cal.loss_after < 1e-6 < cal.loss_before
    assert cal.param_rel_error(gpu_t, st_t) < 1e-2
    # error report: every stencil's predicted-vs-measured error collapses
    for name in cal.stencils:
        assert cal.errors_after[name] < 1e-2
        assert cal.errors_after[name] < cal.errors_before[name]


def test_noisy_fit_still_converges_near_truth():
    gpu_t, st_t = _truth()
    recs = synthetic_records(gpu_t, st_t, noise=0.05, seed=7)
    cal = fit_machine_params(recs, gpu0=MAXWELL_GPU, stencils0=STENCILS)
    assert cal.loss_after < cal.loss_before
    assert cal.param_rel_error(gpu_t, st_t) < 0.15


def test_fit_drops_infeasible_records_and_requires_some():
    recs = synthetic_records(MAXWELL_GPU)
    bad = MeasurementRecord(
        stencil="heat2d", size=(64, 64, 1, 4), tiles=(8, 33, 2, 1, 1),
        time_s=1.0, hw=(16.0, 128.0, 96.0),
    )
    cal = fit_machine_params(recs + [bad], gpu0=MAXWELL_GPU)
    assert cal.n_dropped == 1 and cal.n_records == len(recs)
    with pytest.raises(ValueError, match="no measurement records"):
        fit_machine_params([])
    with pytest.raises(ValueError, match="infeasible"):
        fit_machine_params([bad])


def test_calibration_result_payload_round_trip_and_apply():
    gpu_t, st_t = _truth()
    cal = fit_machine_params(
        synthetic_records(gpu_t, st_t), gpu0=MAXWELL_GPU, iters=50
    )
    back = CalibrationResult.from_payload(cal.to_payload())
    assert back.gpu == cal.gpu
    assert back.stencils == cal.stencils
    assert back.errors_after == cal.errors_after
    # calibrated identities are routable as distinct targets
    assert back.calibrated_gpu().name == "gtx980-cal"
    wl = back.calibrated_workload()
    assert wl.name == "paper-uniform-cal"
    assert {c.stencil.name for c in wl.cells} == set(STENCILS)
    assert all(
        c.stencil.c_iter == back.stencils[c.stencil.name].c_iter for c in wl.cells
    )
    with pytest.raises(KeyError, match="not calibrated"):
        back.calibrated_workload(["nosuch"])


def test_fit_on_real_harness_records_improves_prediction():
    """A tiny real measurement run (interpret mode) will not match a GPU
    model closely, but the refit must still cut the log-space loss --
    the predict -> measure -> refit loop improves, end to end."""
    from repro.measure.harness import measure_grid

    grid = {
        "heat2d": [
            {"shape": (48, 64), "steps": 4,
             "tiles": {"t_s1": 8, "t_s2": 32, "t_t": 2, "k": 1, "t_s3": 1}},
            {"shape": (96, 128), "steps": 4,
             "tiles": {"t_s1": 16, "t_s2": 64, "t_t": 2, "k": 2, "t_s3": 1}},
        ],
    }
    run = measure_grid(grid, warmup=1, repeats=2, interpret=True)
    cal = fit_machine_params(run, iters=300)
    assert cal.loss_after < cal.loss_before
    assert set(cal.stencils) == {"heat2d"}


# ---------------------------------------------------------------------------
# store integration (kind="measurement"/"calibration" artifacts)
# ---------------------------------------------------------------------------
def test_store_json_artifacts_round_trip_and_dedupe(tmp_path):
    from repro.service import ArtifactStore

    store = ArtifactStore(str(tmp_path))
    run = MeasurementRun(
        records=[
            MeasurementRecord(
                stencil="heat2d", size=(64, 64, 1, 4), tiles=(8, 32, 2, 1, 1),
                time_s=2e-3, hw=(16.0, 128.0, 96.0),
            )
        ],
        gpu_name="gtx980", backend="cpu", interpret=True,
    )
    art = store.put_json(
        "measurement", run.to_payload(), routing={"gpu": "gtx980"}
    )
    assert art.kind == "measurement"
    assert MeasurementRun.from_payload(art.payload).records == run.records
    # content addressing: same payload -> same key; any change -> new key
    assert store.put_json("measurement", run.to_payload()).key == art.key
    other = run.to_payload()
    other["note"] = "different"
    assert store.put_json("measurement", other).key != art.key
    # routing rows carry the kind and never pretend to be sweeps
    rows = {r["key"]: r for r in store.entries()}
    assert rows[art.key]["kind"] == "measurement"
    assert rows[art.key]["gpu"] == "gtx980"
    with pytest.raises(ValueError, match="manifest-only"):
        store.put_json("sweep", {})
    with pytest.raises(ValueError, match="manifest-only"):
        store.put_json("nosuch", {})
