"""Execution-time-model tests: physical bounds, feasibility, monotonicities."""

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st  # soft dep: skips, not errors

from repro.core.timemodel import (
    MAXWELL_GPU,
    STENCILS,
    ProblemSize,
    feasible,
    stencil_gflops,
    stencil_time,
)

SIZE2D = ProblemSize(s1=4096, s2=4096, t=1024)
SIZE3D = ProblemSize(s1=512, s2=512, s3=512, t=256)


def _t(st_name, size, n_sm, n_v, m_sm, **sw):
    spec = STENCILS[st_name]
    return float(
        stencil_time(
            spec, MAXWELL_GPU, size, n_sm, n_v, m_sm,
            sw.get("t_s1", 4), sw.get("t_s2", 64), sw.get("t_t", 16),
            sw.get("k", 2), sw.get("t_s3", 1),
        )
    )


def test_infeasible_is_inf():
    # footprint of a 2-array (4+2*64+2)x(1024+2) fp32 tile >> 12 kB
    assert _t("jacobi2d", SIZE2D, 16, 128, 12, t_s2=1024, t_t=64) == np.inf
    # odd t_T violates the hybrid-hexagonal evenness constraint (eq. 15)
    assert _t("jacobi2d", SIZE2D, 16, 128, 96, t_t=15) == np.inf
    # t_S2 not a warp multiple (eq. 13)
    assert _t("jacobi2d", SIZE2D, 16, 128, 96, t_s2=48) == np.inf
    # k beyond MTB_SM (eq. 10)
    assert _t("jacobi2d", SIZE2D, 16, 128, 480, k=64) == np.inf


def test_compute_roofline_never_exceeded():
    """GFLOP/s can never exceed flops_pt * n_SM * n_V / C_iter (lane bound)."""
    spec = STENCILS["jacobi2d"]
    rng = np.random.default_rng(0)
    for _ in range(200):
        n_sm = int(rng.integers(2, 33))
        n_v = int(rng.integers(1, 65)) * 32
        m_sm = float(rng.choice([48, 96, 192, 480]))
        sw = dict(
            t_s1=int(rng.integers(1, 33)),
            t_s2=int(rng.integers(1, 17)) * 32,
            t_t=int(rng.integers(1, 33)) * 2,
            k=int(rng.integers(1, 17)),
        )
        t = _t("jacobi2d", SIZE2D, n_sm, n_v, m_sm, **sw)
        if not np.isfinite(t):
            continue
        g = stencil_gflops(spec, SIZE2D, t)
        bound = spec.flops_per_point * n_sm * n_v / spec.c_iter / 1e9
        assert g <= bound * (1 + 1e-9)


def test_memory_roofline_never_exceeded():
    """Effective DRAM traffic (one footprint per tile) can't beat BW."""
    spec = STENCILS["jacobi2d"]
    # huge compute power so memory is binding
    t = _t("jacobi2d", SIZE2D, 32, 2048, 480, t_s1=8, t_s2=128, t_t=32, k=2)
    assert np.isfinite(t)
    # traffic >= points / (t_T * W * t_S2) tiles * footprint
    from repro.core.timemodel import footprint_bytes

    fp = float(footprint_bytes(spec, MAXWELL_GPU, 8, 128, 32, 1))
    w = 8 + 32
    n_tiles = (SIZE2D.points / (32 * w * 128))
    assert t >= 0.5 * n_tiles * fp / MAXWELL_GPU.bw_gmem  # phase rounding slack


def test_more_sms_never_hurts_much():
    """Scaling coarse parallelism with fixed tiles should not slow down."""
    t8 = _t("jacobi2d", SIZE2D, 8, 128, 96)
    t16 = _t("jacobi2d", SIZE2D, 16, 128, 96)
    t32 = _t("jacobi2d", SIZE2D, 32, 128, 96)
    assert t16 <= t8 * 1.01
    assert t32 <= t16 * 1.01


def test_3d_stencil_runs_and_is_finite():
    t = _t("heat3d", SIZE3D, 16, 128, 192, t_s1=2, t_s2=32, t_t=8, k=1, t_s3=4)
    assert np.isfinite(t) and t > 0
    g = float(stencil_gflops(STENCILS["heat3d"], SIZE3D, t))
    assert 1.0 < g < 1e5


@settings(max_examples=150, deadline=None)
@given(
    n_sm=st.sampled_from([2, 8, 16, 32]),
    n_v=st.sampled_from([32, 128, 512, 2048]),
    m_sm=st.sampled_from([12, 48, 96, 480]),
    t_s1=st.integers(1, 64),
    t_s2=st.sampled_from([32, 64, 128, 256, 512, 1024]),
    t_t=st.sampled_from([2, 4, 8, 16, 32, 64]),
    k=st.integers(1, 32),
)
def test_time_positive_iff_feasible(n_sm, n_v, m_sm, t_s1, t_s2, t_t, k):
    spec = STENCILS["heat2d"]
    ok = bool(
        feasible(spec, MAXWELL_GPU, n_sm, n_v, m_sm, t_s1, t_s2, t_t, k)
    )
    t = float(
        stencil_time(spec, MAXWELL_GPU, SIZE2D, n_sm, n_v, m_sm, t_s1, t_s2, t_t, k)
    )
    if ok:
        assert np.isfinite(t) and t > 0
    else:
        assert t == np.inf


@settings(max_examples=100, deadline=None)
@given(
    t_t=st.sampled_from([2, 4, 8, 16, 32]),
    scale=st.sampled_from([2, 4]),
)
def test_work_scaling(t_t, scale):
    """Property: scaling the time extent scales T_alg ~linearly (same tiles)."""
    small = ProblemSize(s1=2048, s2=2048, t=512)
    big = ProblemSize(s1=2048, s2=2048, t=512 * scale)
    t1 = _t("jacobi2d", small, 16, 128, 96, t_t=t_t)
    t2 = _t("jacobi2d", big, 16, 128, 96, t_t=t_t)
    assert t2 == pytest.approx(t1 * scale, rel=0.02)


class _UlpOff(np.ndarray):
    """Division that lands one ulp off the correctly rounded quotient, as
    an accelerator's reciprocal-based f32 divide may."""

    direction = np.inf

    def __truediv__(self, other):
        q = np.asarray(self) / np.asarray(other)
        return np.nextafter(q, self.direction)


@pytest.mark.parametrize("direction", [np.inf, -np.inf])
def test_ceil_div_exact_under_inexact_division(direction):
    from repro.core.timemodel import _ceil_div

    a = np.array([4096.0, 100.0, 96.0, 1.0, 2048.0], np.float32)
    b = np.array([128.0, 32.0, 32.0, 1.0, 6.0], np.float32)
    want = np.ceil(a.astype(np.float64) / b)
    off = a.view(_UlpOff)
    off.direction = direction
    np.testing.assert_array_equal(np.asarray(_ceil_div(np, off, b)), want)
    np.testing.assert_array_equal(_ceil_div(np, a, b), want)
