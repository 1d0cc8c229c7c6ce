"""The comparison that decides ``correct``: a sound run passes it, and the
control and each fault the cells can have fail it.

A run is driven through ``harness.run_cell`` as on the chip, with the look
for a TPU skipped, on the paper's workload, lattices and limits over a
small hardware space (64 designs). The faults are planted in the program
underneath the run:

* stale state: ``codesign()`` hands back its previous result unchanged;
* half the batch: the sweep solves only the first half of the hardware
  points and repeats them for the rest;
* the exchange between chips left out (four virtual CPU devices, the
  sharded engine): only the first device's shard reaches the host;
* an answer altered where it is produced: the query's claimed GFLOP/s of
  the best design is 1% off.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import harness
import oracle

SMALL_SPACE = {"n_sm": {"start": 2, "stop": 8, "step": 2}, "n_v": {"start": 32, "stop": 256, "step": 32},
               "m_sm": [48, 96], "max_area": 650.0, "points": 64}


def small_root(tmp_path, chips=1):
    """A checkout-like root whose BENCHMARK.json holds one small cell."""
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    with open(os.path.join(harness.BENCH, "configs", "paper-gtx980.json")) as f:
        cfg = json.load(f)
    cfg.update(name="small", hardware_space=SMALL_SPACE)
    with open(tmp_path / "small.json", "w") as f:
        json.dump(cfg, f)
    spec["configs"] = [{"name": "small", "source": "test", "file": "small.json", "reduced": [], "why": "test"}]
    spec["workloads"] = [{"name": "small.cold", "config": "small", "traffic": "cold", "chips": chips, "why": "test"}]
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(spec, f)
    return str(tmp_path)


def run(root, seed=20261016, seconds=0.0, trace=False):
    return harness.run_cell("small.cold", seed, seconds, trace, time.perf_counter(),
                            require_tpu=False, root=root, say=lambda s: None)


def test_sound_run_is_correct(tmp_path):
    out = run(small_root(tmp_path))
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 3 and out["failed"] == 0
    assert set(out["metrics"]) == {"cold_question_s", "setup_s"}
    assert list(out)[-1] == "checks"


def test_large_seed_and_trace_run(tmp_path):
    out = run(small_root(tmp_path), seed=2**31 + 12345, trace=True)
    assert out["correct"], out["checks"]
    assert out["device"]["window_s"] > 0
    assert {"codesign_s", "store_put_s"} <= set(out["metrics"])


def test_control_fails_the_limits(tmp_path):
    """The reference in bfloat16, put in the program's place, reads above
    a limit on every checked question."""
    import jax.numpy as jnp

    found = harness.load_cell("small.cold", small_root(tmp_path))
    harness.start_jax(1, require_tpu=False)
    loop_mod = harness.load_module(os.path.join(harness.BENCH, "loops", "cold_question.py"), "loop")
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


def _stale(monkeypatch):
    from repro.service import server

    real, last = server.codesign, {}

    def codesign(*args, **kwargs):
        if "result" not in last:
            last["result"] = real(*args, **kwargs)
        return last["result"]

    monkeypatch.setattr(server, "codesign", codesign)


def _half_batch(monkeypatch):
    from repro.core import sweep

    real = sweep.sweep_cells

    def sweep_cells(st, gpu, sizes, n_sm, n_v, m_sm, lattice=None, chunk=None):
        h = len(n_sm)
        k = (h + 1) // 2
        t, i = real(st, gpu, sizes, n_sm[:k], n_v[:k], m_sm[:k], lattice, chunk)
        return np.concatenate([t, t], axis=1)[:, :h], np.concatenate([i, i], axis=1)[:, :h]

    monkeypatch.setattr(sweep, "sweep_cells", sweep_cells)


def _answer_altered(monkeypatch):
    from repro.service.query import QueryEngine

    real = QueryEngine._finalize

    def finalize(self, *args, **kwargs):
        resp = real(self, *args, **kwargs)
        resp.best_gflops *= 1.01
        return resp

    monkeypatch.setattr(QueryEngine, "_finalize", finalize)


@pytest.mark.parametrize("fault", [_stale, _half_batch, _answer_altered],
                         ids=["stale_state", "half_batch", "answer_altered"])
def test_fault_makes_run_incorrect(tmp_path, monkeypatch, fault):
    fault(monkeypatch)
    out = run(small_root(tmp_path))
    assert not out["correct"], out


def test_fault_late_in_window_makes_run_incorrect(tmp_path, monkeypatch):
    """State that goes stale only after the window's first questions (past
    the early picks) is caught by the late and last checked questions."""
    from repro.service import server

    real, calls = server.codesign, []

    def codesign(*args, **kwargs):
        calls.append(None)
        if len(calls) <= 8:  # the warm-up question and the window's first seven
            codesign.last = real(*args, **kwargs)
        return codesign.last

    monkeypatch.setattr(server, "codesign", codesign)
    root = small_root(tmp_path)
    out = run(root, seconds=1.0)
    assert out["attempted"] > 8 and not out["correct"], out
    monkeypatch.setattr(server, "codesign", real)
    assert run(root, seconds=1.0)["correct"]


EXCHANGE_LEFT_OUT = r"""
import json, sys, time
sys.path[:0] = [sys.argv[1], sys.argv[2]]
import numpy as np
import harness
from repro.core import sweep

real = sweep.sweep_cells_sharded
def sharded(st, gpu, sizes, n_sm, n_v, m_sm, lattice=None, chunk=None, devices=None):
    t, i = real(st, gpu, sizes, n_sm, n_v, m_sm, lattice, chunk, devices)
    share = -(-t.shape[1] // 4)
    t, i = t.copy(), i.copy()
    t[:, share:], i[:, share:] = np.inf, -1
    return t, i
if sys.argv[4] == "fault":
    sweep.sweep_cells_sharded = sharded
out = harness.run_cell("small.cold", 7, 0.0, False, time.perf_counter(), require_tpu=False,
                       root=sys.argv[3], say=lambda s: None)
print(json.dumps({"correct": out["correct"], "checks": out["checks"]}))
"""


@pytest.mark.parametrize("mode", ["sound", "fault"])
def test_exchange_between_chips_left_out(tmp_path, mode):
    """Four virtual devices take engine="auto" to the sharded path; without
    the gather the run is not correct."""
    root = small_root(tmp_path, chips=4)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-c", EXCHANGE_LEFT_OUT, harness.BENCH,
         os.path.join(harness.ROOT, "src"), root, mode],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["correct"] == (mode == "sound"), out


def test_refuses_without_tpu(tmp_path):
    """On the CPU the harness prints no result and exits 2."""
    proc = subprocess.run(
        [sys.executable, os.path.join(harness.BENCH, "run.py"), "--workload",
         "paper-gtx980.cold", "--seed", "1", "--seconds", "1"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 2 and proc.stdout == "", (proc.stdout, proc.stderr)


def test_refuses_without_program(tmp_path):
    """A directory with only BENCHMARK.json and bench/ exits non-zero."""
    import shutil

    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache", "__pycache__", "tests"))
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "paper-gtx980.cold", "--seed", "1",
         "--seconds", "1"], cwd=tmp_path, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0 and proc.stdout == "", (proc.stdout, proc.stderr)


def test_same_seed_same_inputs_and_large_seeds():
    with open(os.path.join(harness.BENCH, "configs", "paper-gtx980.json")) as f:
        cfg = json.load(f)
    space = oracle.hardware_space(cfg)
    for seed in (0, 5, 2**31 + 7, 2**40 + 3, -3):
        a, b = oracle.permuted(space, seed, 1), oracle.permuted(space, seed, 1)
        assert all(np.array_equal(a[k], b[k]) for k in a)
        assert sorted(a["n_sm"]) == sorted(space["n_sm"])
    a, b = oracle.permuted(space, 5, 1), oracle.permuted(space, 5, 2)
    assert not np.array_equal(a["n_v"], b["n_v"])
