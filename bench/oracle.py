"""Plain reference for the cold codesign question, and the comparison that
decides ``correct``.

Everything here is computed from a configuration file (``configs/*.json``)
alone and imports nothing of the program under test: the hardware space,
the seeded permutation that makes each question cold, the column sample a
question is checked on, the eq.-18 time model in float64 NumPy, and the
reduction that names the best design. The model follows the hybrid-
hexagonal tiling model the program documents (paper arXiv:1712.04892
eqs. 9-18 with the PPoPP'17 time model); each formula is written out once
below, without the program's static pruning, chunking or batching.

The comparison reads what the timed path persisted (``cell_time`` and
``cell_tile_idx`` read back from the store, and the hardware columns
stored beside them) at a sample of hardware columns, and the answered best
design, and reduces them to two numbers:

* ``cell_err`` -- over every sampled (cell, hardware) entry, the largest of
  the relative error of the stored optimum time and the relative amount by
  which the stored tile choice is slower than the reference's optimum. An
  entry whose feasibility differs, whose tile index is out of range, or
  whose stored hardware point is not the one the question asked about
  reads ``inf``.
* ``best_err`` -- for the answered best design: the largest of the relative
  error of the claimed GFLOP/s and the relative amount by which the
  reference finds a better design among the sampled columns within the
  area budget. A missing answer, or a named point that is not the asked
  point at that index, reads ``inf``.
"""

from __future__ import annotations

import itertools
import math
import os
from typing import Dict, Iterator, List, Sequence, Tuple

import numpy as np

SW_NAMES = ("t_s1", "t_s2", "t_t", "k", "t_s3")
HW_NAMES = ("n_sm", "n_v", "m_sm", "area")


def seed_words(seed: int, *stream: int) -> List[int]:
    """Entropy words for ``np.random.default_rng``: any whole number (also
    past 32 or 64 bits, or negative) maps to non-negative 32-bit words."""
    words = []
    n = seed & ((1 << 128) - 1)
    for _ in range(4):
        words.append(n & 0xFFFFFFFF)
        n >>= 32
    return words + [int(s) & 0xFFFFFFFF for s in stream]


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng(seed_words(seed, *stream))


# ---------------------------------------------------------------------------
# inputs from the configuration
# ---------------------------------------------------------------------------
def _axis(spec) -> np.ndarray:
    if isinstance(spec, dict):
        return np.arange(spec["start"], spec["stop"] + 1, spec["step"], dtype=np.float64)
    return np.asarray(spec, np.float64)


def hardware_space(cfg: dict) -> Dict[str, np.ndarray]:
    """Every design point within the area budget, in the enumeration order
    of the paper's sweep: n_SM outermost, then n_V, then M_SM. Designs are
    cache-less (paper section V.A), so L1 = L2 = 0 in the area term."""
    hs, am = cfg["hardware_space"], cfg["area_model"]
    n_sm, n_v, m_sm = np.meshgrid(
        _axis(hs["n_sm"]), _axis(hs["n_v"]), _axis(hs["m_sm"]), indexing="ij"
    )
    n_sm, n_v, m_sm = n_sm.ravel(), n_v.ravel(), m_sm.ravel()
    area = (
        am["c_vu"] * n_sm * n_v
        + am["c_r"] * am["r_vu"] * n_sm * n_v
        + am["c_m"] * m_sm * n_sm
        + am["c_l1"] * am["l1_smpair"] * n_sm
        + am["c_l2"] * am["l2_kb"]
        + am["c_sm"] * n_sm
        + am["c_0"]
    )
    keep = (area <= hs["max_area"]) & (area >= hs.get("min_area", 0.0))
    out = {"n_sm": n_sm[keep], "n_v": n_v[keep], "m_sm": m_sm[keep], "area": area[keep]}
    if len(out["n_sm"]) != hs["points"]:
        raise ValueError(
            f"{cfg['name']}: hardware space holds {len(out['n_sm'])} points, "
            f"the configuration states {hs['points']}"
        )
    return out


def permuted(space: Dict[str, np.ndarray], seed: int, question: int) -> Dict[str, np.ndarray]:
    """The hardware points of one question: the whole space in an order
    drawn from (seed, question). Same work and shapes for every question,
    a new content address each time."""
    perm = rng_for(seed, 1, question).permutation(len(space["n_sm"]))
    return {k: v[perm] for k, v in space.items()}


def cells(cfg: dict) -> List[dict]:
    """Workload cells in order: each stencil over the size grid (S, T) with
    T <= S; 3-D stencils take S for all three extents. Uniform mix."""
    sizes = cfg["sizes"]
    out = []
    for st in cfg["stencils"]:
        for s in sizes["s"]:
            for t in sizes["t"]:
                if t <= s:
                    out.append({
                        "stencil": st,
                        "s1": s, "s2": s, "s3": s if st["dims"] == 3 else 1, "t": t,
                    })
    for c in out:
        c["freq"] = 1.0 / len(out)
    if len(out) != sizes["cells"]:
        raise ValueError(f"{cfg['name']}: {len(out)} cells, the configuration states {sizes['cells']}")
    return out


def lattice_grid(lattice: dict) -> Dict[str, np.ndarray]:
    """All tile candidates in the artifact's index order: the product of
    the value lists in the order t_s1, t_s2, t_t, k, t_s3."""
    combos = np.array(list(itertools.product(*(lattice[k] for k in SW_NAMES))), np.float64)
    return {k: combos[:, j] for j, k in enumerate(SW_NAMES)}


def cell_flops(cell: dict) -> float:
    return cell["stencil"]["flops_per_point"] * float(cell["s1"]) * cell["s2"] * cell["s3"] * cell["t"]


# ---------------------------------------------------------------------------
# the time model (float64 on NumPy; any array namespace and dtype)
# ---------------------------------------------------------------------------
def stencil_time(st: dict, gpu: dict, cell: dict, hw: Dict, sw: Dict, xp=np, dtype=np.float64):
    """T_alg in seconds for every (hardware, tile) pair that ``hw`` and
    ``sw`` broadcast to; infeasible pairs are +inf.

    Hexagonal tiles on the (T, S1) plane of average width t_s1 + r t_t; one
    threadblock of t_s2 threads per tile, each walking t_s3 points in 3-D.
    k co-resident blocks per SM share its n_V lanes; a wavefront phase
    issues its tiles in batches of k n_SM, each batch taking the longer of
    its compute and the global-memory traffic of its tiles' footprints; two
    phases per band of t_t time steps, each paying the launch overhead.
    """
    f = lambda v: xp.asarray(v, dtype)
    r = st["radius"]
    n_sm, n_v, m_sm = f(hw["n_sm"]), f(hw["n_v"]), f(hw["m_sm"])
    t_s1, t_s2, t_t, k, t_s3 = (f(sw[name]) for name in SW_NAMES)
    s1, s2, s3, t_total = f(cell["s1"]), f(cell["s2"]), f(cell["s3"]), f(cell["t"])

    depth = t_s3 + 2.0 * r if st["dims"] == 3 else xp.ones_like(t_s3)
    footprint = st["n_arrays"] * (t_s1 + 2.0 * r * t_t + 2.0 * r) * (t_s2 + 2.0 * r) * depth * gpu["bytes_per_word"]
    w_avg = t_s1 + r * t_t

    t_compute = st["c_iter"] * t_t * w_avg * t_s3 * xp.ceil(k * t_s2 / n_v)
    tiles = xp.ceil(xp.ceil(s1 / w_avg) / 2.0) * xp.ceil(s2 / t_s2)
    if st["dims"] == 3:
        tiles = tiles * xp.ceil(s3 / t_s3)
    tiles = xp.maximum(tiles, 1.0)
    concurrent = xp.minimum(k * n_sm, tiles)
    batches = xp.ceil(tiles / (k * n_sm))
    t_batch = xp.maximum(t_compute, concurrent * footprint / gpu["bw_gmem"])
    t_alg = 2.0 * xp.ceil(t_total / t_t) * (batches * t_batch + gpu["launch_overhead"])

    ok = (
        (k * footprint <= m_sm * 1024.0)
        & (k <= gpu["max_threadblocks_per_sm"])
        & (t_s2 <= gpu["max_threads_per_block"])
        & (k * t_s2 <= gpu["max_threads_per_sm"])
        & (t_t % 2 == 0)
        & (t_s2 % 32 == 0)
    )
    return xp.where(ok, t_alg, xp.inf)


def cell_tables(cfg: dict, hw_cols: Dict[str, np.ndarray], xp=np, dtype=np.float64,
                block: int = 128) -> Iterator[Tuple[int, np.ndarray]]:
    """Per cell, the (S, L) table of candidate times at the S hardware
    columns given, in blocks of ``block`` columns (as float64 NumPy)."""
    grids = {d: lattice_grid(cfg["lattices"][d]) for d in ("2d", "3d")}
    n = len(hw_cols["n_sm"])
    for ci, cell in enumerate(cells(cfg)):
        st = cell["stencil"]
        g = grids["3d" if st["dims"] == 3 else "2d"]
        sw = {name: g[name][None, :] for name in SW_NAMES}
        parts = []
        for lo in range(0, n, block):
            hw = {name: hw_cols[name][lo:lo + block, None] for name in ("n_sm", "n_v", "m_sm")}
            parts.append(np.asarray(stencil_time(st, cfg["gpu"], cell, hw, sw, xp, dtype), np.float64))
        yield ci, np.concatenate(parts, axis=0)


def optimum(table: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """(time, tile index) of the first fastest candidate per column;
    +inf / -1 where no candidate is feasible."""
    idx = np.argmin(table, axis=1)
    t = table[np.arange(table.shape[0]), idx]
    return t, np.where(np.isfinite(t), idx, -1)


# ---------------------------------------------------------------------------
# column sample and comparison
# ---------------------------------------------------------------------------
def sample_columns(n_hw: int, seed: int, question: int, n_random: int,
                   must: Sequence[int]) -> np.ndarray:
    """Sorted, distinct hardware columns to check: ``must`` (the answered
    best and the program's own top designs) plus ``n_random`` drawn from
    (seed, question)."""
    rng = rng_for(seed, 2, question)
    pick = rng.choice(n_hw, size=min(n_random, n_hw), replace=False)
    return np.unique(np.concatenate([np.asarray(must, np.int64), pick.astype(np.int64)]))


def weighted_gflops(cfg: dict, times: np.ndarray) -> np.ndarray:
    """(S,) workload GFLOP/s from a (C, S) matrix of per-cell times."""
    cs = cells(cfg)
    freqs = np.array([c["freq"] for c in cs])
    flops = np.array([cell_flops(c) for c in cs])
    return (freqs @ flops) / (freqs @ times) / 1.0e9


def entry_err(table: np.ndarray, t_ref: np.ndarray, t_got: np.ndarray,
              i_got: np.ndarray) -> np.ndarray:
    """(S,) per-column errors of one cell's stored optima against the
    reference's (S, L) candidate table and its optimum times ``t_ref``."""
    feas = np.isfinite(t_ref)
    err = np.zeros(len(t_ref))
    bad = (np.isfinite(t_got) != feas) | ((i_got >= 0) != feas)
    bad |= feas & ((i_got < 0) | (i_got >= table.shape[1]))
    ok = feas & ~bad
    t_at = table[np.nonzero(ok)[0], i_got[ok]]
    err[ok] = np.maximum(np.abs(t_got[ok] - t_ref[ok]), t_at - t_ref[ok]) / t_ref[ok]
    err[bad] = np.inf
    return err


def best_err(asked: Dict[str, np.ndarray], g_ref: np.ndarray, answer: dict,
             budget: float) -> float:
    """The ``best_err`` of one answer against the reference's GFLOP/s at the
    sampled columns."""
    pos, claimed = answer["pos"], answer["gflops"]
    if pos is None or not math.isfinite(claimed):
        return math.inf
    for name in HW_NAMES:
        if float(answer["point"].get(name, math.nan)) != float(asked[name][pos]):
            return math.inf
    g = np.where((asked["area"] <= budget) & np.isfinite(g_ref), g_ref, -np.inf)
    if not np.isfinite(g[pos]):
        return math.inf
    top = float(g.max())
    return float(max(abs(claimed - g[pos]), top - g[pos]) / top)


def compare(cfg: dict, asked: Dict[str, np.ndarray], budget: float,
            answers: Dict[str, dict]) -> Dict[str, Dict[str, float]]:
    """``cell_err`` and ``best_err`` of each answer to one question.

    ``asked`` holds the hardware points the question asked about at the
    sampled columns. Each answer gives ``hw`` (the points it stored there),
    ``time``/``idx`` (its (C, S) optima and tile indices), ``pos`` (the
    position of its best design among the columns, None for no answer),
    ``gflops`` (its claim for that design) and ``point`` (the design it
    names). The reference runs once, cell by cell, for all answers.
    ``cell_err_finite`` is ``cell_err`` over the entries that read finite:
    what the errors are where feasibility and the stored point agree."""
    n = len(asked["n_sm"])
    bad_hw = {}
    for key, a in answers.items():
        bad_hw[key] = np.zeros(n, bool)
        for name in HW_NAMES:
            bad_hw[key] |= np.asarray(a["hw"][name], np.float64) != asked[name]
    worst = {key: 0.0 for key in answers}
    finite = {key: 0.0 for key in answers}
    ref_time = np.empty((len(cells(cfg)), n))
    for ci, table in cell_tables(cfg, asked):
        t_ref, _ = optimum(table)
        ref_time[ci] = t_ref
        for key, a in answers.items():
            err = entry_err(table, t_ref, a["time"][ci], a["idx"][ci])
            err[bad_hw[key]] = np.inf
            worst[key] = max(worst[key], float(err.max(initial=0.0)))
            finite[key] = max(finite[key], float(err[np.isfinite(err)].max(initial=0.0)))
    with np.errstate(invalid="ignore", divide="ignore"):
        g_ref = weighted_gflops(cfg, ref_time)
    return {key: {"cell_err": worst[key], "best_err": best_err(asked, g_ref, a, budget),
                  "cell_err_finite": finite[key]}
            for key, a in answers.items()}


def control_answer(cfg: dict, asked: Dict[str, np.ndarray], xp, dtype,
                   budget: float) -> dict:
    """The reference computed in a lower precision, put in the program's
    place at the sampled columns: its optima, and the design it would
    answer among those columns with its own GFLOP/s claim (an answer in
    the form :func:`compare` takes)."""
    tables = list(cell_tables(cfg, asked, xp=xp, dtype=dtype))
    times = np.empty((len(tables), len(asked["n_sm"])))
    idx = np.empty(times.shape, np.int64)
    for ci, table in tables:
        times[ci], idx[ci] = optimum(table)
    with np.errstate(invalid="ignore", divide="ignore"):
        g = weighted_gflops(cfg, times)
    g = np.where((asked["area"] <= budget) & np.isfinite(g), g, -np.inf)
    pos = int(np.argmax(g))
    return {"hw": asked, "time": times, "idx": idx, "pos": pos, "gflops": float(g[pos]),
            "point": {name: float(asked[name][pos]) for name in HW_NAMES}}


def readback_columns(art_dir: str, cols: np.ndarray) -> Tuple[np.ndarray, np.ndarray, Dict[str, np.ndarray]]:
    """The persisted artifact's optima and hardware columns at ``cols``,
    read from its files (``cell_time.npy`` and ``arrays.npz``)."""
    cell_time = np.load(os.path.join(art_dir, "cell_time.npy"), mmap_mode="r")
    with np.load(os.path.join(art_dir, "arrays.npz")) as z:
        idx = np.asarray(z["cell_tile_idx"][:, cols])
        hw = {name: np.asarray(z[f"hw_{name}"][cols]) for name in HW_NAMES}
    return np.asarray(cell_time[:, cols]), idx, hw


def top_columns(cfg: dict, art_dir: str, budget: float, n: int) -> np.ndarray:
    """The ``n`` columns the persisted matrix itself ranks fastest within
    the budget: where a wrong best design would hide."""
    cell_time = np.load(os.path.join(art_dir, "cell_time.npy"), mmap_mode="r")
    with np.load(os.path.join(art_dir, "arrays.npz")) as z:
        area = np.asarray(z["hw_area"])
    with np.errstate(invalid="ignore", divide="ignore"):
        g = weighted_gflops(cfg, np.asarray(cell_time))
    g = np.where((area <= budget) & np.isfinite(g), g, -np.inf)
    n = min(n, len(g))
    return np.argpartition(-g, n - 1)[:n]
