"""repro.service: artifact store round-trip, content-addressed keys,
engine-free warm queries (the acceptance property), microbatching vs the
sequential oracle, what-ifs, and LRU eviction."""

import dataclasses
import os
import shutil
import threading
import zipfile

import numpy as np
import pytest

from repro.core import MAXWELL, MAXWELL_GPU, codesign, enumerate_hw_space
from repro.core.pareto import pareto_mask, pareto_mask_batched
from repro.core.workload import paper_workload
from repro.service import (
    ArtifactStore,
    CodesignServer,
    QueryEngine,
    QueryRequest,
    artifact_spec,
    spec_key,
)
from repro.service import store as store_mod

#: small spaces keep the sweeps in test time; stride 32 ~ 160 points.
STRIDE = 32


def small_hw(step=STRIDE):
    return enumerate_hw_space(MAXWELL, max_area=650.0).downsample(step)


@pytest.fixture(scope="module")
def built():
    """One shared (store, server, fresh result) build for the module --
    the expensive part happens once."""
    import tempfile

    root = tempfile.mkdtemp(prefix="svcstore-")
    store = ArtifactStore(root)
    hw = small_hw()
    srv = CodesignServer(store, hw=hw, engine="auto", batch_window=0.0)
    srv.ensure_artifact()
    fresh = codesign(paper_workload(), hw=hw, engine="auto")
    return store, srv, fresh


# ---------------------------------------------------------------------------
# store: round-trip + keys
# ---------------------------------------------------------------------------
def test_artifact_round_trip_bit_identical(built):
    store, srv, fresh = built
    art = store.get(srv.key)
    assert art is not None
    res = art.to_result()
    np.testing.assert_array_equal(res.weighted_time(), fresh.weighted_time())
    np.testing.assert_array_equal(res.gflops(), fresh.gflops())
    np.testing.assert_array_equal(res.pareto(), fresh.pareto())
    np.testing.assert_array_equal(np.asarray(res.cell_time), fresh.cell_time)
    np.testing.assert_array_equal(
        np.asarray(res.cell_tile_idx), fresh.cell_tile_idx
    )
    # stored narrow, read back at the width the caller built it with
    assert art.cell_tile_idx.dtype == np.int64
    assert np.asarray(res.cell_tile_idx).dtype == np.int64
    # reconstructed workload/lattices decode tiles like the original
    ci, hi = 0, int(np.nonzero(fresh.cell_tile_idx[0] >= 0)[0][0])
    assert res.tiles_for(ci, hi) == fresh.tiles_for(ci, hi)


def narrowest_int(a):
    """The narrowest signed-integer dtype that holds ``a``'s range."""
    return next(
        np.dtype(dt) for dt in (np.int8, np.int16, np.int32, np.int64)
        if np.iinfo(dt).min <= a.min() and a.max() <= np.iinfo(dt).max
    )


@pytest.mark.parametrize(
    "member", ["cell_tile_idx", "hw_n_sm", "hw_n_v", "hw_m_sm", "hw_area"]
)
def test_arrays_npz_members_are_stored_uncompressed_and_narrow(built, member):
    """Each member of a fresh ``arrays.npz`` is written without deflate;
    an integer member at the narrowest width that holds its range, a float
    member at float64; the handle reads each back at its built dtype."""
    store, srv, fresh = built
    art = store.get(srv.key)
    path = os.path.join(art.path, "arrays.npz")
    with zipfile.ZipFile(path) as zf:
        info = zf.getinfo(f"{member}.npy")
    assert info.compress_type == zipfile.ZIP_STORED
    if member == "cell_tile_idx":
        built_as, read, want = (fresh.cell_tile_idx, art.cell_tile_idx,
                                narrowest_int(fresh.cell_tile_idx))
    else:
        built_as, read = getattr(fresh.hw, member[3:]), art.hw_column(member[3:])
        want = np.dtype(np.float64)
    with np.load(path) as z:
        on_disk = z[member]
    assert on_disk.dtype == want
    np.testing.assert_array_equal(on_disk, built_as)
    assert read.dtype == np.asarray(built_as).dtype
    np.testing.assert_array_equal(read, built_as)


def test_artifact_written_compressed_at_int64_still_reads(built, tmp_path):
    """An artifact whose ``arrays.npz`` was written the earlier way
    (deflated, int64 argmins) reads unchanged: same key, same values."""
    store, srv, fresh = built
    old = ArtifactStore(str(tmp_path))
    src = store.get(srv.key).path
    dst = os.path.join(old.root, os.path.basename(src))
    shutil.copytree(src, dst)
    path = os.path.join(dst, "arrays.npz")
    np.savez_compressed(
        path,
        cell_tile_idx=np.asarray(fresh.cell_tile_idx, np.int64),
        **{f"hw_{n}": getattr(fresh.hw, n) for n in ("n_sm", "n_v", "m_sm", "area")},
    )
    with zipfile.ZipFile(path) as zf:
        assert {i.compress_type for i in zf.infolist()} == {zipfile.ZIP_DEFLATED}
    art = old.get(srv.key)
    assert art is not None and art.key == srv.key
    assert art.cell_tile_idx.dtype == np.int64
    np.testing.assert_array_equal(art.cell_tile_idx, fresh.cell_tile_idx)
    for name in ("n_sm", "n_v", "m_sm", "area"):
        col = art.hw_column(name)
        assert col.dtype == np.float64
        np.testing.assert_array_equal(col, getattr(fresh.hw, name))
    res = art.to_result()
    np.testing.assert_array_equal(np.asarray(res.cell_time), fresh.cell_time)
    np.testing.assert_array_equal(res.cell_tile_idx, fresh.cell_tile_idx)
    np.testing.assert_array_equal(res.gflops(), fresh.gflops())


@pytest.mark.parametrize(
    "hi,dtype",
    [(-1, np.int8), (127, np.int8), (2879, np.int16), (32767, np.int16),
     (32768, np.int32), (2**31, np.int64)],
)
def test_argmins_narrow_by_their_own_range(built, tmp_path, hi, dtype):
    """-1 (infeasible) survives narrowing, and the width follows the
    largest index: above 32,767 the argmins are stored as int32 and read
    back exactly."""
    _, _, fresh = built
    idx = np.full(fresh.cell_tile_idx.shape, -1, np.int64)
    idx[0, 0] = hi
    idx[-1, -1] = min(hi, 3)
    result = dataclasses.replace(fresh, cell_tile_idx=idx)
    art = ArtifactStore(str(tmp_path)).put(result, engine="auto")
    with np.load(os.path.join(art.path, "arrays.npz")) as z:
        assert z["cell_tile_idx"].dtype == np.dtype(dtype)
    assert art.cell_tile_idx.dtype == np.int64
    np.testing.assert_array_equal(art.cell_tile_idx, idx)
    np.testing.assert_array_equal(art.to_result().cell_tile_idx, idx)


def test_store_key_tracks_hardware_spec(built):
    store, srv, _ = built
    wl = paper_workload()
    base = store.key_for(wl, MAXWELL_GPU, small_hw(), "auto")
    assert base == srv.key
    # same spec -> same key (deterministic content address)
    assert store.key_for(wl, MAXWELL_GPU, small_hw(), "auto") == base
    # a changed hardware space MUST move the key (collision would serve a
    # matrix computed for different hardware points)
    assert store.key_for(wl, MAXWELL_GPU, small_hw(step=16), "auto") != base
    hw2 = enumerate_hw_space(MAXWELL, max_area=500.0).downsample(STRIDE)
    assert store.key_for(wl, MAXWELL_GPU, hw2, "auto") != base
    # so do workload, engine, and format-version changes
    assert store.key_for(paper_workload(["heat2d"]), MAXWELL_GPU, small_hw(), "auto") != base
    assert store.key_for(wl, MAXWELL_GPU, small_hw(), "numpy") != base
    spec = artifact_spec(wl, MAXWELL_GPU, small_hw(), "auto")
    spec["format_version"] += 1
    assert spec_key(spec) != base
    # frequencies are deliberately NOT in the key: re-weighting is free
    reweighted = paper_workload(name="paper-uniform")
    assert store.key_for(reweighted, MAXWELL_GPU, small_hw(), "auto") == base


def test_stale_format_version_reads_as_miss(built, monkeypatch):
    store, srv, _ = built
    assert store.get(srv.key) is not None
    monkeypatch.setattr(store_mod, "FORMAT_VERSION", store_mod.FORMAT_VERSION + 1)
    assert store.get(srv.key) is None  # rebuilt, never mis-served


def test_key_is_engine_invariant_for_bit_identical_engines(built):
    """The key records the engine's matrix family (repro.core.engines):
    "auto" keys as the jax engine it builds with -- which the sharded
    engine shares, so an artifact built on an 8-device host serves a
    1-device host -- below the floor as the numpy oracle it takes there,
    and the float64 oracle keeps a key of its own. "sharded" is not a
    value a caller picks."""
    store, _, _ = built
    wl = paper_workload()
    k_jax = store.key_for(wl, MAXWELL_GPU, small_hw(), "jax")
    assert store.key_for(wl, MAXWELL_GPU, small_hw(), "auto") == k_jax
    assert store.key_for(wl, MAXWELL_GPU, small_hw(), "numpy") != k_jax
    tiny = small_hw()
    tiny = type(tiny)(tiny.n_sm[:3], tiny.n_v[:3], tiny.m_sm[:3], tiny.area[:3])
    assert (store.key_for(wl, MAXWELL_GPU, tiny, "auto")
            == store.key_for(wl, MAXWELL_GPU, tiny, "numpy"))
    with pytest.raises(ValueError, match="unknown engine"):
        store.key_for(wl, MAXWELL_GPU, small_hw(), "sharded")


def test_put_same_key_reuses_winner_without_restaging(built):
    """The build lock's re-check: a second put of an already-stored key
    returns the existing artifact and leaves its files untouched."""
    import os

    store, srv, fresh = built
    art = store.get(srv.key)
    manifest_path = os.path.join(art.path, "manifest.json")
    mtime = os.stat(manifest_path).st_mtime_ns
    again = store.put(fresh, engine="auto")
    assert again.key == srv.key
    assert os.stat(manifest_path).st_mtime_ns == mtime  # no re-stage
    assert os.path.exists(os.path.join(store.root, f".lock-{srv.key}"))


@pytest.mark.skipif(
    store_mod.fcntl is None, reason="no fcntl: build_lock degrades to a no-op"
)
def test_build_lock_excludes_across_processes(built, subprocess_env):
    """Cross-process exclusion: while this process holds the build lock, a
    child process must block on it (and proceed after release)."""
    import subprocess
    import sys
    import time as _time

    store, _, _ = built
    child = """
import sys
from repro.service.store import ArtifactStore
store = ArtifactStore(sys.argv[1])
print("WAITING", flush=True)
with store.build_lock(sys.argv[2]):
    print("ACQUIRED", flush=True)
"""
    key = "lock-contention-test"
    with store.build_lock(key):
        with store.build_lock(key):  # reentrant within the process
            pass
        proc = subprocess.Popen(
            [sys.executable, "-c", child, store.root, key],
            stdout=subprocess.PIPE, text=True, env=subprocess_env,
        )
        assert proc.stdout.readline().strip() == "WAITING"
        _time.sleep(0.3)  # give the child time to (wrongly) acquire
        assert proc.poll() is None, "child acquired a held exclusive lock"
    out, _ = proc.communicate(timeout=30)
    assert "ACQUIRED" in out  # released lock handed over cleanly


# ---------------------------------------------------------------------------
# acceptance: warm queries never touch a sweep engine
# ---------------------------------------------------------------------------
def test_warm_query_is_engine_free_and_exact(built, monkeypatch):
    store, _, fresh = built

    def boom(*a, **k):  # noqa: ARG001
        raise AssertionError("sweep engine invoked on the warm path")

    import importlib

    # repro.core re-exports the codesign *function* under the submodule's
    # name, so `import repro.core.codesign` would bind the function
    codesign_mod = importlib.import_module("repro.core.codesign")
    solver_mod = importlib.import_module("repro.core.solver")
    server_mod = importlib.import_module("repro.service.server")

    monkeypatch.setattr(solver_mod, "solve_cell", boom)
    monkeypatch.setattr(codesign_mod, "solve_cell", boom)
    monkeypatch.setattr(codesign_mod, "codesign", boom)
    monkeypatch.setattr(server_mod, "codesign", boom)
    sweep_mod = importlib.import_module("repro.core.sweep")
    monkeypatch.setattr(sweep_mod, "sweep_cell", boom)
    monkeypatch.setattr(sweep_mod, "sweep_cells", boom)

    # a NEW server over the same store: key computed from the spec alone
    srv = CodesignServer(store, hw=small_hw(), engine="auto", batch_window=0.0)
    assert srv.warm

    rng = np.random.default_rng(7)
    names = [st.name for st in fresh.workload.stencils]
    assert len(names) == 6
    for _ in range(3):
        w = rng.uniform(0.1, 1.0, size=6)
        freqs = dict(zip(names, w))
        resp = srv.query(QueryRequest(freqs=freqs, max_area=500.0))
        # oracle: the same mix through the in-process result, resolved to a
        # cell vector with the engine's exact arithmetic (bit-equality is
        # part of the contract, so the oracle must not re-order the math)
        vec = np.zeros(len(fresh.workload.cells))
        for name, wt in freqs.items():
            cells = [i for i, c in enumerate(fresh.workload.cells)
                     if c.stencil.name == name]
            base = np.array([fresh.workload.cells[i].freq for i in cells])
            vec[cells] = float(wt) * base / base.sum()
        vec /= vec.sum()
        i_ref, g_ref = fresh.best(max_area=500.0, freqs=vec)
        assert resp.best_index == i_ref
        assert resp.best_gflops == pytest.approx(g_ref, rel=0, abs=0)
        # the unbudgeted front must equal CodesignResult.pareto exactly (a
        # budgeted request fronts only the subspace it may buy from, which
        # the fresh API has no analogue for)
        resp_p = srv.query(QueryRequest(freqs=freqs, pareto=True))
        pareto_ref = np.nonzero(fresh.pareto(vec))[0]
        np.testing.assert_array_equal(resp_p.pareto_indices, pareto_ref)
    assert srv.stats["artifact_builds"] == 0


# ---------------------------------------------------------------------------
# queries: top-k, what-if, batched pareto
# ---------------------------------------------------------------------------
def test_top_k_is_sorted_and_within_budget(built):
    _, srv, fresh = built
    resp = srv.query(QueryRequest(max_area=450.0, top_k=5))
    assert 1 <= len(resp.top_k) <= 5
    gs = [r["gflops"] for r in resp.top_k]
    assert gs == sorted(gs, reverse=True)
    assert all(r["area"] <= 450.0 for r in resp.top_k)
    assert resp.top_k[0]["index"] == resp.best_index
    i_ref, g_ref = fresh.best(max_area=450.0)
    assert resp.best_index == i_ref


def test_what_if_fix_restricts_subspace(built):
    _, srv, _ = built
    resp = srv.query(QueryRequest(fix={"n_sm": 16.0}))
    assert resp.best_point["n_sm"] == 16
    assert resp.baseline_best_index is not None
    # the restricted best can never beat the unrestricted best
    assert resp.best_gflops <= resp.baseline_best_gflops + 1e-12


def test_infeasible_constraints_signal_not_fallback(built):
    """An empty budget/fix subspace must answer best_index=-1 with empty
    top_k -- never an arbitrary design that violates the constraints."""
    _, srv, _ = built
    for req in (
        QueryRequest(fix={"n_sm": 17.0}),  # odd n_SM: not in the grid
        QueryRequest(max_area=1.0),  # below every design's area
    ):
        resp = srv.query(req)
        assert resp.best_index == -1
        assert resp.best_point == {}
        assert resp.top_k == []
        assert resp.best_gflops == -np.inf


def test_unknown_stencil_is_rejected_without_poisoning(built):
    _, srv, _ = built
    with pytest.raises(KeyError, match="not in artifact"):
        srv.query(QueryRequest(freqs={"nosuch": 1.0}))
    # server still serves afterwards
    assert np.isfinite(srv.query(QueryRequest()).best_gflops)


def test_pareto_mask_batched_matches_sequential():
    rng = np.random.default_rng(3)
    cost = rng.uniform(100, 650, size=200)
    cost[::17] = cost[0]  # exercise equal-cost ties
    perf = rng.uniform(10, 1e4, size=(5, 200))
    perf[2, ::13] = np.inf
    perf[3, ::11] = np.nan
    got = pareto_mask_batched(cost, perf)
    for b in range(5):
        np.testing.assert_array_equal(got[b], pareto_mask(cost, perf[b]))


# ---------------------------------------------------------------------------
# microbatching: concurrent queries vs the sequential oracle
# ---------------------------------------------------------------------------
def test_concurrent_microbatched_queries_match_sequential(built):
    store, _, fresh = built
    # two servers over the same artifact: separate LRUs, so the batched
    # server really exercises the stacked (B, C) @ (C, H) matmul instead of
    # replaying rows the sequential pass cached
    srv_seq = CodesignServer(store, hw=small_hw(), engine="auto", batch_window=0.0)
    srv = CodesignServer(store, hw=small_hw(), engine="auto", batch_window=0.05)
    srv.ensure_artifact()
    names = [st.name for st in fresh.workload.stencils]
    rng = np.random.default_rng(11)
    reqs = [
        QueryRequest(
            freqs=dict(zip(names, rng.uniform(0.1, 1.0, size=6))),
            max_area=float(rng.uniform(350, 650)),
            top_k=3,
            pareto=(i % 2 == 0),
        )
        for i in range(8)
    ]
    sequential = [srv_seq.query(r) for r in reqs]

    out = [None] * len(reqs)
    barrier = threading.Barrier(len(reqs))

    def worker(i):
        barrier.wait()
        out[i] = srv.query(reqs[i])

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(len(reqs))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    for got, want in zip(out, sequential):
        assert got.best_index == want.best_index
        assert got.best_gflops == pytest.approx(want.best_gflops, rel=1e-12)
        assert [r["index"] for r in got.top_k] == [r["index"] for r in want.top_k]
        if want.pareto_indices is not None:
            np.testing.assert_array_equal(got.pareto_indices, want.pareto_indices)
    # the rendezvous actually batched (8 threads released together, 50 ms
    # window): at least one batch carried more than one request
    assert srv.stats["max_batch"] > 1
    assert srv.stats["queries"] >= len(reqs)


def test_one_bad_request_does_not_poison_the_batch(built):
    store, _, _ = built
    srv = CodesignServer(store, hw=small_hw(), engine="auto", batch_window=0.05)
    srv.ensure_artifact()
    results = {}
    barrier = threading.Barrier(2)

    def good():
        barrier.wait()
        results["good"] = srv.query(QueryRequest(max_area=500.0))

    def bad():
        barrier.wait()
        try:
            srv.query(QueryRequest(freqs={"nosuch": 1.0}))
        except KeyError as e:
            results["bad"] = e

    ts = [threading.Thread(target=good), threading.Thread(target=bad)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert isinstance(results["bad"], KeyError)
    assert np.isfinite(results["good"].best_gflops)


# ---------------------------------------------------------------------------
# LRU
# ---------------------------------------------------------------------------
def test_lru_hit_and_eviction(built):
    store, srv, _ = built
    art = store.get(srv.key)
    eng = QueryEngine(art, lru_size=2)
    names = art.stencil_names
    reqs = [QueryRequest(freqs={names[i]: 1.0}) for i in range(4)]
    base = [eng.query(r) for r in reqs]
    assert eng.lru.hits == 0 and eng.lru.misses == 4
    assert len(eng.lru) == 2  # capacity bound held
    assert eng.lru.evictions == 2
    # the two most recent mixes are hits; results identical to first pass
    for r, want in zip(reqs[2:], base[2:]):
        got = eng.query(r)
        assert got.cached
        assert got.best_index == want.best_index
        assert got.best_gflops == want.best_gflops
    assert eng.lru.hits == 2
    # evicted mixes recompute to the same answer
    again = eng.query(reqs[0])
    assert not again.cached
    assert again.best_index == base[0].best_index
    assert again.best_gflops == base[0].best_gflops


def test_use_cache_false_bypasses_lru(built):
    store, srv, _ = built
    eng = QueryEngine(store.get(srv.key), lru_size=8)
    r = QueryRequest(use_cache=False)
    a, b = eng.query(r), eng.query(r)
    assert not a.cached and not b.cached
    assert len(eng.lru) == 0
    assert a.best_index == b.best_index
