"""LM op-graph cells (repro.core.lmcells): the vectorized sweep engine vs
the plain-scalar oracle (bit-exact in float64), the oracle vs
``lm_roofline`` (term-level equality for the standard ops), jax engine
agreement, family dispatch through ``codesign()``, and artifact
round-trip bit-identity + content-key stability through the store."""

import dataclasses

import numpy as np
import pytest

from repro.configs import get_arch
from repro.core import sweep
from repro.core.codesign import codesign
from repro.core.lmcells import (
    LM_GPU_NAME,
    enumerate_lm_hw_space,
    lm_cell_roofline,
    lm_codesign,
    lm_sw_lattice,
    lm_workload,
    resolve_lm_engine,
)
from repro.core.lmtime import MeshPlan, lm_roofline
from repro.core.workload import Workload, paper_workload
from repro.service.store import ArtifactStore

#: float32 evaluation noise bound for the jax engine (numpy is exact).
RTOL = 1e-5


@pytest.fixture(scope="module")
def cfgs():
    """Reduced same-family variants keep cell constants small and fast;
    mixtral brings the MoE dispatch op into the workload."""
    return [get_arch("llama3-8b").reduced(), get_arch("mixtral-8x22b").reduced()]


@pytest.fixture(scope="module")
def wl(cfgs):
    return lm_workload(archs=cfgs, name="lm-test")


@pytest.fixture(scope="module")
def hw():
    return enumerate_lm_hw_space(max_chips=32)


@pytest.fixture(scope="module")
def oracle(wl, hw):
    return lm_codesign(wl, hw=hw, engine="numpy")


def _brute_force(cell, lat, point):
    """min over the software lattice, feasibility-masked, via the scalar
    oracle -- the reference the vectorized engines must reproduce."""
    times = []
    for j in range(len(lat)):
        plan = lat.plan(point["pod"], point["data"], point["model"], j)
        r = lm_cell_roofline(cell, plan)
        times.append(r["bound_s"] if r["feasible"] else np.inf)
    return times


def test_workload_shape(wl):
    assert wl.family == "lm"
    ops = {c.op for c in wl.cells}
    assert ops == {"prefill", "decode", "train", "moe_dispatch"}
    assert len(wl.cells) == 7  # 3 dense + 4 MoE
    np.testing.assert_allclose(sum(c.freq for c in wl.cells), 1.0)
    # decode cells carry a real KV-cache footprint; others none
    for c in wl.cells:
        assert (c.kv_bytes > 0) == (c.op == "decode")


def test_numpy_engine_is_bit_exact_vs_scalar_oracle(wl, hw, oracle):
    """Exhaustive (cell x hw x sw) check: identical expression order makes
    the vectorized float64 grid *bit*-equal to the scalar oracle."""
    for ci, cell in enumerate(wl.cells):
        lat = lm_sw_lattice(cell.op)
        for hi in range(len(hw)):
            times = _brute_force(cell, lat, hw.point(hi))
            t = min(times)
            if np.isfinite(t):
                assert oracle.cell_time[ci, hi] == t, (cell.label, hi)
                # the recorded plan achieves the optimum
                j = int(oracle.cell_plan_idx[ci, hi])
                assert times[j] == t
            else:
                assert oracle.cell_time[ci, hi] == np.inf
                assert oracle.cell_plan_idx[ci, hi] == -1


def test_scalar_oracle_mirrors_lm_roofline(cfgs, wl):
    """For prefill/decode/train the cell oracle must reproduce
    ``lm_roofline`` term for term (moe_dispatch is defined in lmcells and
    has no lmtime twin)."""
    by_model = {c.name: c for c in cfgs}
    plans = [
        MeshPlan(1, 2, 2),
        MeshPlan(1, 1, 8, microbatches=2, remat="none"),
        MeshPlan(2, 4, 2, microbatches=4, remat="full", fsdp=True,
                 compress_grads=True),
    ]
    checked = 0
    for cell in wl.cells:
        if cell.op == "moe_dispatch":
            continue
        cfg = by_model[cell.model]
        for plan in plans:
            a = lm_cell_roofline(cell, plan)
            b = lm_roofline(cfg, cell.shape, plan, cell.n_params, cell.n_active)
            for key in ("compute_s", "memory_s", "collective_s", "bound_s",
                        "hbm_bytes"):
                assert a[key] == b[key], (cell.label, plan, key)
            assert a["dominant"] == b["dominant"]
            assert a["fits"] == b["fits"]
            checked += 1
    assert checked == 6 * len(plans)


@pytest.mark.skipif(not sweep.HAVE_JAX, reason="jax not installed")
def test_jax_engine_matches_numpy(wl, hw, oracle):
    jres = lm_codesign(wl, hw=hw, engine="jax")
    feas = np.isfinite(oracle.cell_time)
    assert np.array_equal(feas, np.isfinite(jres.cell_time))
    assert np.allclose(jres.cell_time[feas], oracle.cell_time[feas], rtol=RTOL)
    # where the f32 argmin differs it must be a tie in the f64 model
    for ci, cell in enumerate(wl.cells):
        lat = lm_sw_lattice(cell.op)
        diff = np.nonzero(feas[ci] & (jres.cell_plan_idx[ci] != oracle.cell_plan_idx[ci]))[0]
        for hi in diff:
            times = _brute_force(cell, lat, hw.point(int(hi)))
            j = int(jres.cell_plan_idx[ci, hi])
            assert times[j] == pytest.approx(oracle.cell_time[ci, hi], rel=RTOL)


def test_engine_resolution():
    assert resolve_lm_engine("numpy") == "numpy"
    assert resolve_lm_engine("auto") in ("numpy", "jax")
    with pytest.raises(ValueError):
        resolve_lm_engine("cuda")


def test_codesign_dispatches_on_family(wl, hw, oracle):
    res = codesign(wl, hw=hw, engine="numpy")
    assert type(res).__name__ == "LMCodesignResult"
    assert np.array_equal(res.cell_time, oracle.cell_time)
    assert np.array_equal(res.cell_plan_idx, oracle.cell_plan_idx)


def test_mixed_family_workload_rejected(wl):
    halved = [
        dataclasses.replace(c, freq=c.freq / 2)
        for c in (*paper_workload().cells, *wl.cells)
    ]
    with pytest.raises(ValueError, match="famil"):
        Workload(name="mixed", cells=tuple(halved))


def test_plan_for_round_trips(wl, hw, oracle):
    ci = next(i for i, c in enumerate(wl.cells) if c.op == "train")
    hi = int(np.nonzero(np.isfinite(oracle.cell_time[ci]))[0][-1])
    plan = oracle.plan_for(ci, hi)
    r = lm_cell_roofline(wl.cells[ci], plan)
    assert r["feasible"]
    assert r["bound_s"] == oracle.cell_time[ci, hi]


def test_artifact_round_trip_bit_identity(tmp_path, wl, hw, oracle):
    store = ArtifactStore(str(tmp_path))
    art = store.put(oracle, engine="numpy")
    # the content key is computable BEFORE any sweep, and stable
    assert art.key == store.key_for_lm(wl, hw, engine="numpy")
    assert art.family == "lm"
    assert store.put(oracle, engine="numpy").key == art.key

    back = art.to_result()
    assert type(back).__name__ == "LMCodesignResult"
    assert np.array_equal(back.cell_time, oracle.cell_time)
    assert np.array_equal(back.cell_plan_idx, oracle.cell_plan_idx)
    assert back.cell_plan_idx.dtype == np.int64
    assert back.gpu_name == oracle.gpu_name == LM_GPU_NAME
    assert [c.label for c in back.workload.cells] == [c.label for c in wl.cells]
    np.testing.assert_array_equal(back.cell_freqs(), oracle.cell_freqs())
    np.testing.assert_array_equal(back.cell_flops(), oracle.cell_flops())
    # the reconstructed cells re-solve to the same plans
    for ci in range(len(wl.cells)):
        hi = int(np.nonzero(np.isfinite(oracle.cell_time[ci]))[0][0])
        assert back.plan_for(ci, hi) == oracle.plan_for(ci, hi)

    md = art.routing()
    assert md["workload"] == "lm-test" and md["family"] == "lm"
    assert md["models"] == sorted({c.model for c in wl.cells})
    assert md["ops"] == ["decode", "moe_dispatch", "prefill", "train"]
    # area IS the chip count for LM sweeps
    np.testing.assert_array_equal(art.hw_area, art.hw_column("chips"))


def test_key_tracks_the_question(tmp_path, wl, cfgs, hw):
    store = ArtifactStore(str(tmp_path))
    base = store.key_for_lm(wl, hw, engine="numpy")
    assert store.key_for_lm(wl, hw, engine="numpy") == base
    smaller = enumerate_lm_hw_space(max_chips=16)
    assert store.key_for_lm(wl, smaller, engine="numpy") != base
    one = lm_workload(archs=cfgs[:1], name="lm-test")
    assert store.key_for_lm(one, hw, engine="numpy") != base
    assert store.key_for_lm(wl, hw, engine="numpy", gpu_name="other") != base


def test_divisibility_infeasibility(cfgs, hw):
    """A global batch that cannot shard over the data axis must surface as
    +inf / plan -1, mirroring meshopt's constraint -- not as a silently
    wrong time."""
    from repro.configs.base import ShapeSpec

    shape = ShapeSpec("decode_b3", 1024, 3, "decode")  # 3 never splits
    wl3 = lm_workload(archs=cfgs[:1], name="gb3",
                      shapes={"decode": shape})
    res = lm_codesign(wl3, hw=hw, engine="numpy")
    ci = next(i for i, c in enumerate(wl3.cells) if c.op == "decode")
    ds = (hw.pod * hw.data).astype(int)
    bad = (3 % ds != 0) & (3 >= ds)
    assert np.all(~np.isfinite(res.cell_time[ci][bad]))
    assert np.all(res.cell_plan_idx[ci][bad] == -1)
