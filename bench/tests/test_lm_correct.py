"""The LM cell's reference and the comparison that decides ``correct``: the
reference agrees with the program's scalar model, a sound run passes the
comparison, and the control and each fault the cell can have fail it.

Runs are driven through ``harness.run_cell`` as on the chip, with the look
for a TPU skipped, on the ``deepseek-v3.lm-cold`` cell itself: its full
widths and its 144 meshes are small enough for the CPU. The faults are
planted in the program underneath the run:

* attention over context dropped from the priced FLOPs;
* routed experts not spread: their group is the model axis alone;
* activations not divided over the model axis: in the traffic of prefill
  and train, or in the train step's HBM fit (which then fits no mesh, and
  the run stops in set-up);
* the grid evaluated in bfloat16 instead of float32;
* the query's claimed GFLOP/s of the best design 1% off.
"""

import dataclasses
import inspect
import json
import os
import time

import numpy as np
import pytest

import harness
import lm_oracle

CELL = "deepseek-v3.lm-cold"


def config():
    with open(os.path.join(harness.BENCH, "configs", "deepseek-v3.json")) as f:
        return json.load(f)


def run(seed=20261018, seconds=0.0, trace=False):
    return harness.run_cell(CELL, seed, seconds, trace, time.perf_counter(),
                            require_tpu=False, say=lambda s: None)


def published_view(cfg, base):
    """A program ``ArchConfig`` restated under the published config keys
    the reference reads."""
    m = cfg.moe
    return dict(
        base, name=cfg.name, hidden_size=cfg.d_model, num_hidden_layers=cfg.n_layers,
        num_attention_heads=cfg.n_heads, q_lora_rank=cfg.attn.q_lora_rank,
        kv_lora_rank=cfg.attn.kv_lora_rank, qk_nope_head_dim=cfg.head_dim_,
        qk_rope_head_dim=cfg.attn.rope_head_dim, v_head_dim=cfg.attn.v_head_dim,
        intermediate_size=cfg.d_ff, moe_intermediate_size=m.d_ff, n_routed_experts=m.n_experts,
        n_shared_experts=m.n_shared, num_experts_per_tok=m.top_k,
        first_k_dense_replace=m.first_dense, moe_layer_freq=m.every, vocab_size=cfg.vocab,
        tie_word_embeddings=cfg.tie_embeddings, num_nextn_predict_layers=int(cfg.mtp),
        capacity_factor=m.capacity_factor,
    )


# ---------------------------------------------------------------------------
# the reference against the program's own model
# ---------------------------------------------------------------------------
def test_config_restates_the_program_architecture():
    from repro.configs import get_arch

    cfg = get_arch(config()["arch"])
    assert published_view(cfg, config()) == dict(config(), name=cfg.name)


@pytest.mark.parametrize("reduced", [True, False], ids=["reduced", "full"])
def test_closed_form_counts_match_eval_shape(reduced):
    """The reference's counts from the published widths against the
    program's ``eval_shape`` counts. The one difference is the router's
    per-expert correction bias, which the published weights hold and the
    program does not (the configuration's ``assumed.router_bias``)."""
    from repro.configs import get_arch
    from repro.models.model import active_params, count_params, routed_expert_params

    cfg = get_arch("deepseek-v3-671b")
    cfg = cfg.reduced() if reduced else cfg
    ref = lm_oracle.param_counts(published_view(cfg, config()))
    bias = (sum(f == "moe" for _, f in cfg.layer_kinds()) + 1) * cfg.moe.n_experts
    assert ref["routed"] == routed_expert_params(cfg)
    assert ref["total"] - count_params(cfg) == bias
    assert ref["active"] - active_params(cfg) == bias
    if not reduced:
        assert bias == 15104


@pytest.mark.parametrize("reduced", [True, False], ids=["reduced", "full-16-meshes"])
def test_scalar_model_matches_the_reference(reduced):
    """Every (cell, mesh, software) entry of the program's scalar model
    against the reference's table: equal feasibility, and times apart by no
    more than the two known differences of their inputs allow: the router
    bias in the parameter counts and the latent cache's int32 write index
    per layer (``assumed.router_bias``, ``assumed.cache_index``)."""
    from repro.configs import get_arch
    from repro.configs.base import ShapeSpec
    from repro.core.lmcells import (enumerate_lm_hw_space, lm_cell_roofline, lm_sw_lattice,
                                    lm_workload)

    arch = get_arch("deepseek-v3-671b")
    if reduced:  # small widths, the published bf16 storage
        arch = dataclasses.replace(arch.reduced(), dtype=arch.dtype)
    ref_cfg = published_view(arch, config())
    if reduced:
        ref_cfg["cells"] = [dict(c, seq_len=64, global_batch=8) for c in ref_cfg["cells"]]
        ref_cfg["hardware_space"] = {"max_chips": 32, "pods": [1, 2],
                                     "points": len(enumerate_lm_hw_space(max_chips=32))}
    space = lm_oracle.hardware_space(ref_cfg)
    if not reduced:
        keep = np.arange(len(space["pod"])) % 9 == 0
        space = {k: v[keep] for k, v in space.items()}
        assert len(space["pod"]) == 16
    shapes = {c["op"]: ShapeSpec(c["shape"], c["seq_len"], c["global_batch"],
                                 "decode" if c["op"] == "moe_dispatch" else c["op"])
              for c in ref_cfg["cells"]}
    wl = lm_workload(archs=[arch], name="t", shapes=shapes)
    assert [c.op for c in wl.cells] == [c["op"] for c in ref_cfg["cells"]]
    ref_cells = lm_oracle.cells(ref_cfg)
    pairs = [(r[k], getattr(c, k)) for r, c in zip(ref_cells, wl.cells)
             for k in ("n_params", "n_active", "kv_bytes")]
    pairs += [(r["n_params"] - r["n_routed"], c.n_params - c.n_routed)
              for r, c in zip(ref_cells, wl.cells)]
    rel = max(abs(a - b) / b for a, b in pairs if b)
    assert 0 < rel < 1e-3
    feasible = []
    for (ci, table), cell in zip(lm_oracle.cell_tables(ref_cfg, space), wl.cells):
        feasible.append(0)
        lat = lm_sw_lattice(cell.op)
        assert table.shape == (len(space["pod"]), len(lat))
        for hi in range(table.shape[0]):
            for j in range(len(lat)):
                r = lm_cell_roofline(cell, lat.plan(int(space["pod"][hi]), int(space["data"][hi]),
                                                    int(space["model"][hi]), j))
                want = table[hi, j]
                assert r["feasible"] == np.isfinite(want), (cell.op, hi, j)
                if r["feasible"]:
                    assert r["bound_s"] == pytest.approx(want, rel=rel), (cell.op, hi, j)
                    feasible[-1] += 1
    assert min(feasible) > 0, feasible


# ---------------------------------------------------------------------------
# a sound run, the control, and the planted faults
# ---------------------------------------------------------------------------
def test_sound_run_is_correct():
    out = run()
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 6 and out["failed"] == 0
    assert set(out["metrics"]) == {"cold_question_s", "setup_s"}
    assert out["checks"]["cell_err"]["value"] < 1e-5


def test_large_seed_and_trace_run():
    out = run(seed=2**31 + 12345, trace=True)
    assert out["correct"], out["checks"]
    assert {"codesign_s", "store_put_s"} <= set(out["metrics"])


def test_control_fails_the_limits():
    """The reference in bfloat16, put in the program's place, reads above
    a limit on every checked question."""
    import jax.numpy as jnp

    found = harness.load_cell(CELL)
    harness.start_jax(1, require_tpu=False)
    loop_mod = harness.load_module(os.path.join(harness.BENCH, "loops", "lm_cold_question.py"),
                                   "lm_loop")
    limits = found["config"]["limits"]
    for seed in (1, 2, 3):
        loop = loop_mod.Loop(found["config"], found["traffic"], seed, lambda s: None)
        try:
            loop.setup()
            loop.window(0.0)
            for rec in loop.checked_questions():
                got = loop.compare(rec, control=(jnp, jnp.bfloat16))
                assert all(got["program"][k] <= limits[k] for k in limits), got
                assert any(got["control"][k] > limits[k] for k in limits), got
        finally:
            loop.close()


def _patch_grid(monkeypatch, old, new):
    """Replace one line of the traced grid body (``lm_cell_roofline``'s
    twin) and drop the compiled grids, so every question uses the fault."""
    from repro.core import lmcells

    src = inspect.getsource(lmcells._grid_times)
    assert src.count(old) >= 1, old
    scope = dict(vars(lmcells))
    exec(src.replace(old, new), scope)
    monkeypatch.setattr(lmcells, "_grid_times", scope["_grid_times"])
    monkeypatch.setattr(lmcells, "_JIT_CACHE", {})


def _attention_dropped(monkeypatch):
    from repro.core import lmcells

    monkeypatch.setattr(lmcells, "attention_flops", lambda cfg, shape: 0.0)


def _experts_not_spread(monkeypatch):
    _patch_grid(monkeypatch, "ep = xp.minimum(data * model, n_experts)",
                "ep = xp.minimum(model, n_experts)")


def _activations_not_divided(monkeypatch):
    _patch_grid(monkeypatch, "* (4.0 - 3.0 * remat) / model", "* (4.0 - 3.0 * remat)")


def _activation_traffic_not_divided(monkeypatch):
    _patch_grid(monkeypatch, "act_traffic = act_traffic / model", "act_traffic = act_traffic")


def _bfloat16_grid(monkeypatch):
    import jax.numpy as jnp

    from repro.core import lmcells

    real = lmcells._jax_grid_fn

    def grid_fn(op):
        fn = real(op)
        return lambda consts, *cols: fn(tuple(jnp.asarray(c, jnp.bfloat16) for c in consts),
                                        *(c.astype(jnp.bfloat16) for c in cols))

    monkeypatch.setattr(lmcells, "_JIT_CACHE", {})
    monkeypatch.setattr(lmcells, "_jax_grid_fn", grid_fn)


def _answer_altered(monkeypatch):
    from repro.service.query import QueryEngine

    real = QueryEngine._finalize

    def finalize(self, *args, **kwargs):
        resp = real(self, *args, **kwargs)
        resp.best_gflops *= 1.01
        return resp

    monkeypatch.setattr(QueryEngine, "_finalize", finalize)


@pytest.mark.parametrize("fault", [_attention_dropped, _experts_not_spread,
                                   _activation_traffic_not_divided, _bfloat16_grid,
                                   _answer_altered],
                         ids=["attention_dropped", "experts_not_spread",
                              "activation_traffic_not_divided", "bfloat16_grid",
                              "answer_altered"])
def test_fault_makes_run_incorrect(monkeypatch, fault):
    fault(monkeypatch)
    out = run()
    assert not out["correct"], out


def test_same_seed_same_meshes_and_large_seeds():
    space = lm_oracle.hardware_space(config())
    assert len(space["pod"]) == 144 and space["area"].max() == 2048
    for seed in (0, 5, 2**31 + 7, 2**40 + 3, -3):
        a, b = lm_oracle.permuted(space, seed, 1), lm_oracle.permuted(space, seed, 1)
        assert all(np.array_equal(a[k], b[k]) for k in a)
        assert sorted(a["area"]) == sorted(space["area"])
    a, b = lm_oracle.permuted(space, 5, 1), lm_oracle.permuted(space, 5, 2)
    assert not np.array_equal(a["model"], b["model"])



def test_activations_not_divided_leave_no_answer(monkeypatch):
    """Without sequence parallelism DeepSeek-V3's train step fits no mesh
    within 2,048 chips, so the query names a design at 0 GFLOP/s. That is
    no answer: the warm-up question fails and the run stops before its
    window, as it does on a program without this cost model."""
    _activations_not_divided(monkeypatch)
    with pytest.raises(RuntimeError, match="warm-up question failed"):
        run()
