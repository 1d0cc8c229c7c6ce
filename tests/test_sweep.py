"""JAX sweep engine vs the NumPy reference oracle (repro.core.solver).

The engines must agree cell-by-cell on the eq.-18 inner solves: identical
feasibility, identical optima up to float32 evaluation noise, and -- where
their argmins differ -- only on exact ties (the jax-chosen candidate must
re-evaluate, in the oracle's float64 model, to the oracle's optimum)."""

import numpy as np
import pytest

from repro.core import MAXWELL, MAXWELL_GPU, STENCILS, ProblemSize, codesign
from repro.core import enumerate_hw_space
from repro.core import sweep
from repro.core.solver import LATTICE_2D, LATTICE_3D, TileLattice, solve_cell
from repro.core.timemodel import stencil_time
from repro.core.workload import paper_workload

#: float32 evaluation noise bound: disagreements beyond this are real bugs.
RTOL = 1e-5


def small_hw(step=16):
    """Downsampled paper hardware space (~300 points)."""
    return enumerate_hw_space(MAXWELL, max_area=650.0).downsample(step)


def assert_argmin_equivalent(st, size, lattice, hw, t_np, i_np, t_jax, i_jax):
    """Engines may pick different candidates only when both achieve the
    oracle optimum (ties); feasibility must match exactly."""
    assert np.array_equal(i_np < 0, i_jax < 0), "feasibility sets differ"
    feas = i_np >= 0
    assert np.allclose(t_jax[feas], t_np[feas], rtol=RTOL)
    g = lattice.grid()
    for h in np.nonzero(feas & (i_np != i_jax))[0]:
        j = i_jax[h]
        t_alt = float(
            stencil_time(
                st, MAXWELL_GPU, size, hw.n_sm[h], hw.n_v[h], hw.m_sm[h],
                g["t_s1"][j], g["t_s2"][j], g["t_t"][j], g["k"][j], g["t_s3"][j],
            )
        )
        assert t_alt == pytest.approx(t_np[h], rel=RTOL), (
            f"hw {h}: jax candidate {j} is not tied with the oracle optimum"
        )


@pytest.mark.parametrize(
    "name,size,lattice",
    [
        ("jacobi2d", ProblemSize(4096, 4096, 1024), LATTICE_2D),
        ("heat2d", ProblemSize(8192, 8192, 2048), LATTICE_2D),
        ("heat3d", ProblemSize(512, 512, 256, s3=512), LATTICE_3D),
    ],
)
def test_sweep_matches_numpy_oracle(name, size, lattice):
    st = STENCILS[name]
    hw = small_hw()
    t_np, i_np = solve_cell(st, MAXWELL_GPU, size, hw.n_sm, hw.n_v, hw.m_sm, lattice)
    t_jax, i_jax = sweep.sweep_cell(
        st, MAXWELL_GPU, size, hw.n_sm, hw.n_v, hw.m_sm, lattice
    )
    assert np.isfinite(t_np).any()  # the comparison must not be vacuous
    assert_argmin_equivalent(st, size, lattice, hw, t_np, i_np, t_jax, i_jax)


def test_sweep_cells_batches_all_sizes_in_one_dispatch():
    """The extra vmap axis: a (P, 4) size batch must reproduce P separate
    sweep_cell calls exactly, for every chunking regime (incl. the scaled
    default and a chunk that does not divide H)."""
    from repro.core.workload import paper_sizes

    st = STENCILS["heat2d"]
    hw = small_hw(step=13)  # not a multiple of any chunk below
    sizes = np.array(
        [(s.s1, s.s2, s.s3, s.t) for s in paper_sizes(st.dims)], np.float64
    )
    refs = [
        sweep.sweep_cell(
            st, MAXWELL_GPU, ProblemSize(s1=r[0], s2=r[1], t=r[3], s3=r[2]),
            hw.n_sm, hw.n_v, hw.m_sm, LATTICE_2D,
        )
        for r in sizes
    ]
    for chunk in (None, 7, 0):
        t, i = sweep.sweep_cells(
            st, MAXWELL_GPU, sizes, hw.n_sm, hw.n_v, hw.m_sm, LATTICE_2D, chunk
        )
        assert t.shape == (len(sizes), len(hw))
        for p, (t_ref, i_ref) in enumerate(refs):
            np.testing.assert_allclose(t[p], t_ref, rtol=0)
            np.testing.assert_array_equal(i[p], i_ref)


@pytest.mark.parametrize("name,lattice", [("heat2d", LATTICE_2D), ("heat3d", LATTICE_3D)])
def test_staged_columns_match_host_columns(name, lattice):
    """Float32 device columns from ``stage_columns`` give the very arrays
    the float64 host columns give: same bits, same dtypes."""
    from repro.core.workload import paper_sizes

    st = STENCILS[name]
    hw = small_hw(step=13)
    sizes = np.array([(s.s1, s.s2, s.s3, s.t) for s in paper_sizes(st.dims)], np.float64)
    cols = (hw.n_sm, hw.n_v, hw.m_sm)
    staged = sweep.stage_columns(*cols)
    assert all(c.dtype == np.float32 and c.shape == (len(hw),) for c in staged)
    want = sweep.sweep_cells(st, MAXWELL_GPU, sizes, *cols, lattice)
    got = sweep.sweep_cells(st, MAXWELL_GPU, sizes, *staged, lattice)
    assert np.isfinite(want[0]).any()
    for w, g in zip(want, got):
        assert g.dtype == w.dtype and np.array_equal(g, w)


def test_codesign_answers_through_sweep_cells_once_a_group(monkeypatch):
    """``codesign()`` calls ``sweep.sweep_cells`` once per stencil group
    and keeps what it answers: a wrapper that answers only half the
    hardware columns, repeated, changes ``cell_time``."""
    wl = paper_workload(["heat2d", "heat3d"])
    hw = small_hw(step=48)
    sound = codesign(wl, hw=hw, engine="jax").cell_time
    real, calls = sweep.sweep_cells, []

    def half_batch(st, gpu, sizes, n_sm, n_v, m_sm, lattice=None, chunk=None):
        calls.append(st.name)
        h = len(n_sm)
        k = (h + 1) // 2
        t, i = real(st, gpu, sizes, n_sm[:k], n_v[:k], m_sm[:k], lattice, chunk)
        return np.concatenate([t, t], axis=1)[:, :h], np.concatenate([i, i], axis=1)[:, :h]

    monkeypatch.setattr(sweep, "sweep_cells", half_batch)
    faulty = codesign(wl, hw=hw, engine="jax").cell_time
    assert sorted(calls) == ["heat2d", "heat3d"]
    assert faulty.shape == sound.shape and not np.array_equal(faulty, sound)


def test_codesign_jax_groups_match_oracle_per_cell():
    """The driver's one-dispatch-per-stencil-family path must equal the
    NumPy per-cell oracle on the full multi-size workload."""
    wl = paper_workload(["heat2d", "heat3d"], name="grouped")
    hw = small_hw(step=48)
    res_jax = codesign(wl, hw=hw, engine="jax")
    res_np = codesign(wl, hw=hw, engine="numpy")
    assert np.array_equal(
        np.isfinite(res_jax.cell_time), np.isfinite(res_np.cell_time)
    )
    feas = np.isfinite(res_np.cell_time)
    np.testing.assert_allclose(
        res_jax.cell_time[feas], res_np.cell_time[feas], rtol=RTOL
    )


def test_chunking_is_invisible():
    """lax.map slab size (incl. padding remainders) must not change results."""
    st = STENCILS["jacobi2d"]
    size = ProblemSize(4096, 4096, 1024)
    hw = small_hw(step=11)  # deliberately not a multiple of any chunk
    ref_t, ref_i = sweep.sweep_cell(
        st, MAXWELL_GPU, size, hw.n_sm, hw.n_v, hw.m_sm, LATTICE_2D, chunk=0
    )
    for chunk in (1, 7, 64, 10**9):
        t, i = sweep.sweep_cell(
            st, MAXWELL_GPU, size, hw.n_sm, hw.n_v, hw.m_sm, LATTICE_2D, chunk=chunk
        )
        np.testing.assert_array_equal(i, ref_i)
        np.testing.assert_allclose(t, ref_t, rtol=0)


def test_infeasible_hardware_marked():
    """A scratchpad too small for any tile must yield +inf / -1, same as
    the oracle."""
    st = STENCILS["heat3d"]
    size = ProblemSize(512, 512, 256, s3=512)
    n_sm, n_v, m_sm = np.array([16.0]), np.array([128.0]), np.array([0.001])
    t_jax, i_jax = sweep.sweep_cell(st, MAXWELL_GPU, size, n_sm, n_v, m_sm, LATTICE_3D)
    t_np, i_np = solve_cell(st, MAXWELL_GPU, size, n_sm, n_v, m_sm, LATTICE_3D)
    assert not np.isfinite(t_np[0]) and i_np[0] == -1
    assert not np.isfinite(t_jax[0]) and i_jax[0] == -1


def test_codesign_engine_parity():
    """Full driver stack: both engines produce the same workload-level
    reductions (weighted time, GFLOP/s, best design) on a small space."""
    wl = paper_workload(["jacobi2d", "heat3d"], name="parity")
    hw = small_hw(step=32)
    res_np = codesign(wl, hw=hw, engine="numpy")
    res_jax = codesign(wl, hw=hw, engine="jax")
    np.testing.assert_allclose(res_jax.weighted_time(), res_np.weighted_time(), rtol=RTOL)
    np.testing.assert_allclose(res_jax.gflops(), res_np.gflops(), rtol=RTOL)
    i_np, g_np = res_np.best(max_area=450.0)
    i_jax, g_jax = res_jax.best(max_area=450.0)
    assert g_jax == pytest.approx(g_np, rel=RTOL)


@pytest.mark.parametrize("engine", ["cuda", "sharded"])
def test_codesign_rejects_unknown_engine(engine):
    """"sharded" is no longer a value a caller picks: "auto" takes the mesh
    engine from the device count. Nor does codesign() take devices=."""
    wl = paper_workload(["jacobi2d"])
    with pytest.raises(ValueError, match="unknown engine"):
        codesign(wl, hw=small_hw(step=64), engine=engine)
    with pytest.raises(TypeError, match="devices"):
        codesign(wl, hw=small_hw(step=64), engine="auto", devices=1)


def test_refine_points_batched():
    """Batched descent: never worse than the lattice optimum, alignment
    constraints intact, and locally exact (no single aligned step helps)."""
    st = STENCILS["heat2d"]
    size = ProblemSize(8192, 8192, 2048)
    hw = small_hw(step=64)
    t0, i0 = sweep.sweep_cell(st, MAXWELL_GPU, size, hw.n_sm, hw.n_v, hw.m_sm, LATTICE_2D)
    feas = np.nonzero(i0 >= 0)[0][:8]
    g = LATTICE_2D.grid()
    sw0 = np.stack([[g[k][i0[h]] for k in sweep.SW_NAMES] for h in feas])
    hw_rows = np.stack([[hw.n_sm[h], hw.n_v[h], hw.m_sm[h]] for h in feas])
    sizes = np.tile((size.s1, size.s2, size.s3, size.t), (len(feas), 1))
    t_ref, sw_ref = sweep.refine_points(st, MAXWELL_GPU, sizes, hw_rows, sw0)
    assert np.all(np.isfinite(t_ref))
    assert np.all(t_ref <= t0[feas] * (1 + 1e-5))
    assert np.all(sw_ref[:, 1] % 32 == 0)  # eq. (13): warp-aligned t_s2
    assert np.all(sw_ref[:, 2] % 2 == 0)  # eq. (15): even t_t
    # local exactness in the float64 oracle model: no aligned step improves
    for p, h in enumerate(feas):
        cur = float(
            stencil_time(
                st, MAXWELL_GPU, size, hw.n_sm[h], hw.n_v[h], hw.m_sm[h],
                *sw_ref[p],
            )
        )
        for d, step in enumerate(sweep.SW_STEPS):
            for delta in (step, -step):
                cand = sw_ref[p].copy()
                cand[d] = max(cand[d] + delta, sweep.SW_MINS[d])
                t_cand = float(
                    stencil_time(
                        st, MAXWELL_GPU, size,
                        hw.n_sm[h], hw.n_v[h], hw.m_sm[h], *cand,
                    )
                )
                assert t_cand >= cur * (1 - 1e-5)


def test_refine_points_zero_rounds_returns_start():
    """max_rounds=0 must return the start points untouched (same contract
    as the oracle refine_point), with their float64 times -- not NaN."""
    st = STENCILS["jacobi2d"]
    size = ProblemSize(4096, 4096, 1024)
    sw0 = np.array([[8.0, 64.0, 16.0, 2.0, 1.0], [4.0, 32.0, 8.0, 1.0, 1.0]])
    hw_rows = np.tile((16.0, 128.0, 96.0), (2, 1))
    sizes = np.tile((size.s1, size.s2, size.s3, size.t), (2, 1))
    t, sw = sweep.refine_points(st, MAXWELL_GPU, sizes, hw_rows, sw0, max_rounds=0)
    np.testing.assert_array_equal(sw, sw0)
    want = [
        float(stencil_time(st, MAXWELL_GPU, size, 16.0, 128.0, 96.0, *row))
        for row in sw0
    ]
    np.testing.assert_allclose(t, want, rtol=1e-12)


def test_sweep_steps_match_oracle_table():
    """The batched descent's step/bound tables are derived from the NumPy
    oracle's _STEPS -- alignment semantics cannot drift apart."""
    from repro.core.solver import _STEPS

    assert sweep.SW_STEPS == tuple(float(_STEPS[k]) for k in sweep.SW_NAMES)
    assert sweep.SW_MINS[0] == 1.0
    assert sweep.SW_MINS[1:] == sweep.SW_STEPS[1:]


def test_result_refine_batches_all_cells():
    """CodesignResult.refine polishes every cell at a reported design point
    and never regresses the lattice optimum."""
    wl = paper_workload(["jacobi2d", "heat3d"], name="refine")
    hw = small_hw(step=32)
    res = codesign(wl, hw=hw, engine="jax")
    i, _ = res.best(max_area=650.0)
    times, tiles = res.refine(i)
    lattice_times = res.cell_time[:, i]
    assert np.all(times <= lattice_times * (1 + 1e-5))
    for ci in range(len(times)):
        if np.isfinite(times[ci]):
            assert set(tiles[ci]) == set(sweep.SW_NAMES)


def test_traceable_time_model_grad_and_vmap():
    """The rewritten time model is a first-class jax citizen: vmap works and
    jit produces the same numbers as the NumPy path."""
    import jax
    import jax.numpy as jnp

    st = STENCILS["jacobi2d"]
    size = ProblemSize(4096, 4096, 1024)

    def f(t_s1):
        return stencil_time(
            st, MAXWELL_GPU, size, 16.0, 128.0, 96.0, t_s1, 64.0, 16.0, 2.0,
            1.0, xp=jnp,
        )

    xs = jnp.arange(1.0, 9.0)
    got = jax.jit(jax.vmap(f))(xs)
    want = stencil_time(
        st, MAXWELL_GPU, size, 16.0, 128.0, 96.0, np.arange(1.0, 9.0), 64.0,
        16.0, 2.0, 1.0,
    )
    np.testing.assert_allclose(np.asarray(got, np.float64), want, rtol=1e-6)
