"""LM op-graph cells (repro.core.lmcells): the vectorized sweep engine vs
the plain-scalar oracle (bit-exact in float64), the oracle vs
``lm_roofline`` (term-level equality for the standard ops), jax engine
agreement, family dispatch through ``codesign()``, and artifact
round-trip bit-identity + content-key stability through the store."""

import dataclasses

import numpy as np
import pytest

from repro.configs import get_arch
from repro.core.codesign import codesign
from repro.core.engines import engine_family
from repro.core.lmcells import (
    LM_GPU_NAME,
    enumerate_lm_hw_space,
    lm_cell_roofline,
    lm_cells_for,
    lm_codesign,
    lm_sw_lattice,
    lm_workload,
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


def test_engine_resolution(wl, hw):
    """The LM grid asks the one rule with no hardware floor: auto is jax
    on any mesh space, however small; "sharded" is not an engine."""
    assert engine_family("numpy") == "numpy"
    assert engine_family("jax") == "jax"
    assert engine_family("auto") == "jax"
    for bad in ("cuda", "sharded"):
        with pytest.raises(ValueError, match="unknown engine"):
            engine_family(bad)
        with pytest.raises(ValueError, match="unknown engine"):
            lm_codesign(wl, hw=hw, engine=bad)


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
    +inf / plan -1 (the shardability constraint) -- not as a silently
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


# ---------------------------------------------------------------------------
# DeepSeek-V3: attention over context, expert parallelism, sequence
# parallelism, and its design question at its published cluster size
# ---------------------------------------------------------------------------
def _context_matmul_flops(fn, args, ctx, need):
    """FLOPs of the ``dot_general`` s in ``fn``'s jaxpr that run over a
    context axis of length ``ctx`` (at least ``need`` of their output and
    contracted dims have that length: 2 for the square score and value
    matmuls of a prefill, 1 for a decode's single query), each scan body
    counted once per iteration."""
    import math

    import jax

    def walk(jaxpr, mult):
        total = 0
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general":
                (lhs_c, _), _ = eqn.params["dimension_numbers"]
                lhs = eqn.invars[0].aval.shape
                out = eqn.outvars[0].aval.shape
                contract = [lhs[i] for i in lhs_c]
                if sum(d == ctx for d in (*out, *contract)) >= need:
                    total += mult * 2 * math.prod(out) * math.prod(contract)
            inner = mult * eqn.params.get("length", 1)
            for v in eqn.params.values():
                for sub in v if isinstance(v, (tuple, list)) else (v,):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        total += walk(sub, inner)
        return total

    return walk(jax.make_jaxpr(fn)(*args).jaxpr, 1)


def _attn_cfg(name):
    cfg = get_arch(name).reduced()
    if cfg.attn.kind == "swa":
        # a window below the prefill length and unequal to every width, so
        # the cap shows and the context axis is found by its length alone
        cfg = dataclasses.replace(cfg, attn=dataclasses.replace(cfg.attn, window=12))
    return cfg


@pytest.mark.parametrize("op", ["prefill", "decode"])
@pytest.mark.parametrize("arch", ["deepseek-v3-671b", "llama3-8b", "mixtral-8x22b"])
def test_attention_term_matches_the_models_own_matmuls(arch, op):
    """The priced attention FLOPs against a direct count of the score and
    value matmuls the reduced model's own forward traces: MLA in its naive
    prefill and absorbed decode forms, full attention, and a sliding window
    (prefill computes the full square under a mask, so its count is scaled
    to the causal, windowed pairs counted here one by one)."""
    import jax
    import jax.numpy as jnp

    from repro.configs.base import ShapeSpec
    from repro.core.lmcells import attention_flops
    from repro.models.model import forward, init_model
    from repro.serve.kvcache import init_caches

    cfg = _attn_cfg(arch)
    params = jax.eval_shape(lambda: init_model(cfg, jax.random.PRNGKey(0)))
    b, s = 2, 21
    w = cfg.attn.window if cfg.attn.kind == "swa" else s
    if op == "prefill":
        tokens = jax.ShapeDtypeStruct((b, s), jnp.int32)
        square = _context_matmul_flops(
            lambda p, t: forward(p, cfg, {"tokens": t}), (params, tokens), s, 2)
        pairs = sum(min(i, w) for i in range(1, s + 1))
        want = square * pairs / s**2
        got = attention_flops(cfg, ShapeSpec("p", s, b, "prefill"))
        assert attention_flops(cfg, ShapeSpec("t", s, b, "train")) == 3.0 * got
    else:
        caches = jax.eval_shape(lambda: init_caches(cfg, b, s))
        token = jax.ShapeDtypeStruct((b, 1), jnp.int32)
        want = _context_matmul_flops(
            lambda p, t, c: forward(p, cfg, {"tokens": t, "cache_index": jnp.int32(5)},
                                    caches=c),
            (params, token, caches), min(s, w), 1)
        got = attention_flops(cfg, ShapeSpec("d", s, b, "decode"))
    assert want > 0
    assert got == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("full", [False, True], ids=["reduced", "full"])
def test_routed_experts_are_the_experts_leaves_of_eval_shape(full):
    """``n_routed`` is what ``eval_shape`` of the real init holds under
    ``experts``: 3 matrices of d x d_ff per expert in each MoE layer and in
    the MTP block, which DeepSeek-V3 builds as an MoE layer."""
    import math

    import jax

    from repro.models.model import init_model

    cfg = get_arch("deepseek-v3-671b")
    cfg = cfg if full else cfg.reduced()
    shapes = jax.eval_shape(lambda: init_model(cfg, jax.random.PRNGKey(0)))
    leaves = jax.tree_util.tree_leaves_with_path(shapes)
    routed = sum(math.prod(x.shape) for path, x in leaves if "'experts'" in jax.tree_util.keystr(path))
    m = cfg.moe
    moe_layers = sum(f == "moe" for _, f in cfg.layer_kinds()) + 1  # + the MTP block
    assert routed == 3 * cfg.d_model * m.d_ff * m.n_experts * moe_layers
    cells = lm_cells_for(cfg)
    assert {c.n_routed for c in cells} == {routed}
    assert {c.moe_n_experts for c in cells} == {m.n_experts}
    # active = total - the experts a token does not visit
    (c0,) = {(c.n_params, c.n_active) for c in cells}
    assert c0[1] == c0[0] - routed // m.n_experts * (m.n_experts - m.top_k)


def _ds_workload(cfg):
    from repro.configs.base import SHAPES, ShapeSpec

    return lm_workload(archs=[cfg], name="deepseek-v3", shapes={
        "prefill": SHAPES["prefill_32k"], "decode": SHAPES["decode_32k"],
        "train": ShapeSpec("train_3072x4k", 4096, 3072, "train")})


@pytest.mark.parametrize("full", [False, True], ids=["reduced", "full-16-meshes"])
def test_deepseek_engines_match_the_scalar_oracle(full):
    """numpy bit-exact and jax within float32 noise (ties allowed) against
    the scalar oracle, for every cell of DeepSeek-V3's question."""
    cfg = get_arch("deepseek-v3-671b")
    wl = _ds_workload(cfg if full else cfg.reduced())
    hw = enumerate_lm_hw_space(max_chips=2048).downsample(9) if full else (
        enumerate_lm_hw_space(max_chips=32))
    if full:
        assert len(hw) == 16
    res = lm_codesign(wl, hw=hw, engine="numpy")
    assert np.isfinite(res.cell_time).any(axis=1).all()
    for ci, cell in enumerate(wl.cells):
        lat = lm_sw_lattice(cell.op)
        for hi in range(len(hw)):
            times = _brute_force(cell, lat, hw.point(hi))
            assert res.cell_time[ci, hi] == min(times), (cell.label, hi)
    jres = lm_codesign(wl, hw=hw, engine="jax")
    feas = np.isfinite(res.cell_time)
    assert np.array_equal(feas, np.isfinite(jres.cell_time))
    assert np.allclose(jres.cell_time[feas], res.cell_time[feas], rtol=RTOL)
    for ci, cell in enumerate(wl.cells):
        lat = lm_sw_lattice(cell.op)
        for hi in np.nonzero(feas[ci] & (jres.cell_plan_idx[ci] != res.cell_plan_idx[ci]))[0]:
            times = _brute_force(cell, lat, hw.point(int(hi)))
            j = int(jres.cell_plan_idx[ci, hi])
            assert times[j] == pytest.approx(res.cell_time[ci, hi], rel=RTOL)


def test_deepseek_is_answered_within_its_training_cluster():
    """At the 2,048 chips of DeepSeek-V3's training cluster the train step
    fits some mesh and the uniform mix names a design feasible for every
    cell; prefill's priced compute is about half attention at 32k."""
    wl = _ds_workload(get_arch("deepseek-v3-671b"))
    res = lm_codesign(wl, max_chips=2048, engine="numpy")
    train = next(i for i, c in enumerate(wl.cells) if c.op == "train")
    assert np.isfinite(res.cell_time[train]).any()
    i, g = res.best(2048)
    assert np.isfinite(g) and g > 0
    assert np.isfinite(res.cell_time[:, i]).all()
    prefill = next(c for c in wl.cells if c.op == "prefill")
    assert 0.45 < prefill.attn_flops / prefill.flops < 0.6


def test_experts_spread_over_the_expert_parallel_group():
    """Routed experts divide over data x model (capped at the expert
    count), so data parallelism alone no longer replicates them, while the
    other weights divide over the model axis only; the dispatch all-to-all
    runs over that group, not the model axis."""
    cfg = get_arch("deepseek-v3-671b").reduced()  # 4 routed experts
    (disp,) = [c for c in lm_cells_for(cfg) if c.op == "moe_dispatch"]
    n_dense = disp.n_params - disp.n_routed
    for data, model, group in ((1, 1, 1), (2, 1, 2), (8, 1, 4), (2, 2, 4), (1, 8, 8)):
        r = lm_cell_roofline(disp, MeshPlan(1, data, model))
        assert r["hbm_bytes"] == pytest.approx(
            2.0 * n_dense / model + 2.0 * disp.n_routed / group), (data, model)
    assert lm_cell_roofline(disp, MeshPlan(1, 1, 1))["collective_s"] == 0.0
    assert lm_cell_roofline(disp, MeshPlan(1, 8, 1))["collective_s"] > 0.0


def test_train_activations_divide_over_the_model_axis():
    """Sequence parallelism: the train step's activation working set at
    model = 4 is a quarter of what it is at model = 1, weights held fixed."""
    (cell,) = [c for c in lm_workload(archs=[get_arch("llama3-8b")]).cells if c.op == "train"]
    free = dataclasses.replace(cell, n_params=0, n_routed=0)
    narrow = lm_cell_roofline(free, MeshPlan(1, 8, 1, microbatches=4, remat="full"))
    wide = lm_cell_roofline(free, MeshPlan(1, 8, 4, microbatches=4, remat="full"))
    assert wide["hbm_bytes"] == pytest.approx(narrow["hbm_bytes"] / 4)


# ---------------------------------------------------------------------------
# the jax engine: inputs staged once a question, every grid fetched at once
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module", params=["default-pair-512", "deepseek-v3-2048"])
def question(request):
    """The two LM questions ``chip_smoke.py`` asks at full widths: the
    default pair over the meshes of up to 512 chips, and DeepSeek-V3's
    question over its 2,048-chip cluster (H = 144)."""
    if request.param == "default-pair-512":
        return lm_workload(), enumerate_lm_hw_space(max_chips=512)
    return _ds_workload(get_arch("deepseek-v3-671b")), enumerate_lm_hw_space(max_chips=2048)


def _per_cell_reference(wl, hw):
    """The jax engine as a per-cell loop evaluates it: one jitted
    :func:`_grid_times` a cell, its 11 constants as Python floats, the
    meshes as (H, 1) and the lattice as (L,) float32 arguments, each grid
    fetched before the next; argmin on the host."""
    import jax
    import jax.numpy as jnp

    from repro.core.lmcells import _cell_consts, _grid_times

    f32 = lambda a: jnp.asarray(np.asarray(a, np.float32))
    times, idx = [], []
    for cell in wl.cells:
        lat = lm_sw_lattice(cell.op)
        grid = jax.jit(lambda *a, op=cell.op: _grid_times(op, *a, jnp))(
            _cell_consts(cell), f32(hw.pod)[:, None], f32(hw.data)[:, None],
            f32(hw.model)[:, None], f32(lat.microbatches), f32(lat.remat_full),
            f32(lat.fsdp), f32(lat.compress))
        grid = np.asarray(grid, np.float64)
        j = np.argmin(grid, axis=1)
        t = grid[np.arange(len(hw)), j]
        times.append(t)
        idx.append(np.where(np.isfinite(t), j, -1))
    return np.array(times), np.array(idx)


def _bits(a):
    return a.dtype, a.shape, a.tobytes()


def test_jax_engine_is_bit_identical_to_per_cell_grids(question):
    wl, hw = question
    res = lm_codesign(wl, hw=hw, engine="jax")
    times, idx = _per_cell_reference(wl, hw)
    assert _bits(res.cell_time) == _bits(times)
    assert _bits(res.cell_plan_idx) == _bits(idx)
    assert np.isfinite(res.cell_time).any(axis=1).all()


def test_permuted_meshes_give_the_permuted_result(question):
    """The benchmark's questions: the whole mesh space in a new order."""
    from repro.core.lmcells import LMHardwareSpace

    wl, hw = question
    res = lm_codesign(wl, hw=hw, engine="jax")
    p = np.random.default_rng(2**31 + 7).permutation(len(hw))
    moved = LMHardwareSpace(hw.pod[p], hw.data[p], hw.model[p], hw.area[p])
    got = lm_codesign(wl, hw=moved, engine="jax")
    assert _bits(got.cell_time) == _bits(np.ascontiguousarray(res.cell_time[:, p]))
    assert _bits(got.cell_plan_idx) == _bits(np.ascontiguousarray(res.cell_plan_idx[:, p]))


def test_one_transfer_for_the_meshes_and_one_a_cell(question, monkeypatch):
    import jax

    wl, hw = question
    real, calls = jax.device_put, []

    def device_put(x, *args, **kwargs):
        calls.append(np.shape(x))
        return real(x, *args, **kwargs)

    monkeypatch.setattr(jax, "device_put", device_put)
    for _ in range(2):  # the first question compiles, the second does not
        calls.clear()
        lm_codesign(wl, hw=hw, engine="jax")
        assert calls == [(3, len(hw))] + [(11,)] * len(wl.cells)
