#!/usr/bin/env python
"""Drive the main path once on a TPU and check what comes out.

Run from the root of a checkout, on a host with a TPU attached:

    python chip_smoke.py            # one chip: every phase below
    python chip_smoke.py --chips 4  # the sharded sweep over four chips,
                                    # against engine="jax" on one, only

Phases (one chip), each printing one line that names what it compared:

* device    -- the backend is a TPU; its kind, count and the JAX version.
* sweep     -- ``service.cli build --engine jax`` for gtx980 and titanx over
  the section IV.B hardware space (six stencils x 16 sizes), each artifact
  checked against ``codesign(engine="numpy")``: feasibility exact, times
  within ``RTOL``, every differing argmin tied with the oracle's optimum.
* serve     -- the gateway over that store, on a thread; ``/v1/query``
  answers over HTTP byte-identical to an in-process ``CodesignServer`` on
  the same artifact, and each best design tied with the numpy oracle's.
* kernels   -- ``measure.cli run --full``: the six tiled Pallas kernels
  compiled over the full grid, each output checked against
  ``kernels/ref.py``; one banded kernel per dimensionality likewise.
* fit       -- ``measure.cli fit`` on that measurement and ``measure.cli
  build`` on the calibrated GPU (the loop running; no gate on the error).
* lm        -- ``lm_codesign`` jax against numpy on the default workload
  (512 chips) and on DeepSeek-V3's question (2,048 chips), the answer
  feasible for every cell; jax bit-identical to one jitted grid a cell
  with its constants as Python floats; a repeat's spans one
  ``lm.dispatch`` a cell, none compiling, then one ``lm.fetch`` of every
  grid, after 1 + C host-to-device copies.
* portfolio -- ``optimize_portfolio_arrays`` jax against numpy, K=3.

Everything runs in this one process (a second process could not open the
chip). Stores, measurements and calibrations go to a fresh directory under
``--out``; nothing else is read. The last line of standard output is one
JSON object naming the device; any failed phase exits non-zero before it.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import re
import shutil
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "src")

GPUS = ("gtx980", "titanx")
#: float32 evaluation noise bound of the compiled engines (tests/test_sweep.py)
RTOL = 1e-5
#: the tiled kernels' documented tolerance (tests/test_pallas_stencils.py)
KERNEL_RTOL = 1e-4
#: every fourth point of the hardware space: K=3 subsets of the dominance
#: survivors stay under the portfolio's enumeration cap
PORTFOLIO_STRIDE = 4


class PhaseFailed(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise PhaseFailed(what)


def say(phase: str, text: str) -> None:
    print(f"[{phase}] {text}", flush=True)


def run_cli(main, argv) -> str:
    """Call a CLI's ``main(argv)`` in this process; return its stdout."""
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        main(argv)
    return buf.getvalue()


def close(got, want, rtol=KERNEL_RTOL) -> float:
    """Largest error over the tolerance ``allclose(rtol, atol=rtol x field
    scale)`` that the kernel tests document; <= 1 passes."""
    import numpy as np

    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    scale = max(1.0, float(np.max(np.abs(want))))
    return float(np.max(np.abs(got - want) / (rtol * scale + rtol * np.abs(want))))


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def phase_device(jax):
    dev = jax.devices()
    say("device", f"platform={dev[0].platform} kind={dev[0].device_kind!r} "
        f"count={len(dev)} jax={jax.__version__}")
    return {"platform": dev[0].platform, "kind": dev[0].device_kind, "count": len(dev)}


def _sweep_ties(res_np, cell_time, cell_idx):
    """(max relative time error, differing argmins, all tied?) of a compiled
    result against the numpy oracle, the rule of tests/test_sweep.py."""
    import numpy as np

    from repro.core.timemodel import stencil_time

    check(np.array_equal(res_np.cell_tile_idx < 0, cell_idx < 0),
          "feasibility sets differ from the oracle")
    feas = res_np.cell_tile_idx >= 0
    rel = np.abs(cell_time[feas] - res_np.cell_time[feas]) / res_np.cell_time[feas]
    diffs = tied = 0
    hw = res_np.hw
    for c, cell in enumerate(res_np.workload.cells):
        cols = np.nonzero(feas[c] & (cell_idx[c] != res_np.cell_tile_idx[c]))[0]
        if cols.size == 0:
            continue
        g = res_np.lattices[c].grid()
        j = cell_idx[c, cols]
        t_alt = stencil_time(
            cell.stencil, res_np.gpu, cell.size, hw.n_sm[cols], hw.n_v[cols],
            hw.m_sm[cols], g["t_s1"][j], g["t_s2"][j], g["t_t"][j], g["k"][j],
            g["t_s3"][j],
        )
        diffs += cols.size
        tied += int(np.sum(np.abs(t_alt - res_np.cell_time[c, cols])
                           <= RTOL * res_np.cell_time[c, cols]))
    return float(rel.max(initial=0.0)), diffs, tied == diffs


def phase_sweep(store_dir, oracle_dir):
    import numpy as np

    from repro.core import codesign, enumerate_hw_space
    from repro.core.timemodel import GPUS_BY_NAME
    from repro.core.workload import paper_workload
    from repro.obs.metrics import get_registry
    from repro.service import ArtifactStore
    from repro.service import cli as service_cli

    arts, oracles = {}, {}
    store = ArtifactStore(store_dir)
    oracle_store = ArtifactStore(oracle_dir)
    hw = enumerate_hw_space()
    for gpu in GPUS:
        t0 = time.perf_counter()
        out = run_cli(service_cli.main,
                      ["build", "--store", store_dir, "--gpu", gpu, "--engine", "jax"])
        t_build = time.perf_counter() - t0
        key = re.search(r"artifact ([0-9a-f]{20})", out).group(1)
        art = store.get(key)
        t0 = time.perf_counter()
        res_np = codesign(paper_workload(), gpu=GPUS_BY_NAME[gpu], hw=hw, engine="numpy")
        t_np = time.perf_counter() - t0
        cell_time = np.asarray(art.cell_time)
        cell_idx = np.asarray(art.cell_tile_idx)
        check(cell_time.shape == res_np.cell_time.shape == (96, len(hw)),
              f"{gpu}: artifact shape {cell_time.shape}")
        worst, diffs, tied = _sweep_ties(res_np, cell_time, cell_idx)
        check(worst <= RTOL, f"{gpu}: max relative time error {worst:.3g} > {RTOL}")
        check(tied, f"{gpu}: a differing argmin is not tied with the oracle")
        say("sweep", f"{gpu}: jax artifact {key} vs numpy oracle, "
            f"{cell_time.shape[0]} cells x {cell_time.shape[1]} hw: feasibility "
            f"exact, max rel time err {worst:.3g} <= {RTOL}, {diffs} differing "
            f"argmins all tied; build {t_build:.2f}s (numpy oracle {t_np:.1f}s) OK")
        arts[gpu] = art
        oracles[gpu] = (res_np, oracle_store.put(res_np, engine="numpy"))
    snap = get_registry().snapshot()["repro_sweep_dispatch_seconds"]["samples"]
    by_phase = {s["labels"]["phase"]: s for s in snap if s["labels"]["engine"] == "jax"}
    parts = [f"{p} {by_phase[p]['count']} dispatches {by_phase[p]['sum']:.3f}s"
             for p in ("compile", "steady") if p in by_phase]
    compiles = get_registry().snapshot()["repro_sweep_compiles_total"]["samples"]
    n = sum(s["value"] for s in compiles if s["labels"]["engine"] == "jax")
    say("sweep", f"jax dispatch seconds ({n:.0f} programs compiled or loaded): "
        + ", ".join(parts))
    return store, arts, oracles


def phase_serve(store_dir, store, arts, oracles):
    from repro.service import CodesignServer, GatewayClient, wire
    from repro.service.gateway import Gateway, serve_http
    from repro.service.query import QueryEngine, QueryRequest

    requests = {
        "one stencil": QueryRequest(freqs={"heat3d": 1.0}, use_cache=False),
        "frequency mix": QueryRequest(freqs={"heat2d": 3.0, "jacobi2d": 1.0},
                                      use_cache=False),
        "top-3 max_area=450 pareto": QueryRequest(max_area=450.0, top_k=3,
                                                  pareto=True, use_cache=False),
    }
    httpd = serve_http(Gateway([store_dir]), port=0)
    thread = threading.Thread(target=httpd.serve_forever, daemon=True)
    thread.start()
    try:
        url = "http://%s:%d" % httpd.server_address[:2]
        client = GatewayClient(url)
        for gpu, art in arts.items():
            local = CodesignServer.from_artifact(store, art, batch_window=0.0)
            numpy_engine = QueryEngine(oracles[gpu][1])
            res_np = oracles[gpu][0]
            for what, req in requests.items():
                raw = client.query_bytes(req, route={"gpu": gpu})
                check(raw == wire.encode_response(local.query(req)),
                      f"{gpu} {what}: HTTP bytes differ from the in-process server")
                got = wire.decode_response(raw)
                want = numpy_engine.query(req)
                wt = res_np.cell_time.T @ numpy_engine.freq_vector(req)
                check(got.best_index >= 0 and abs(wt[got.best_index] - wt[want.best_index])
                      <= RTOL * wt[want.best_index],
                      f"{gpu} {what}: best design {got.best_index} not tied with "
                      f"the oracle's {want.best_index}")
                say("serve", f"{gpu} {what}: HTTP bytes == in-process server "
                    f"({len(raw)} B), best hw {got.best_index} "
                    f"{'==' if got.best_index == want.best_index else 'tied with'} "
                    f"numpy oracle's {want.best_index} OK")
    finally:
        httpd.shutdown()
        httpd.server_close()
        thread.join(timeout=30)


def phase_kernels(store_dir, jax):
    import jax.numpy as jnp

    from repro.kernels import ops
    from repro.kernels.pallas_stencils import run_tiled
    from repro.kernels.ref import run_ref
    from repro.measure import MeasurementRun
    from repro.measure import cli as measure_cli
    from repro.measure.harness import default_grid

    t0 = time.perf_counter()
    out = run_cli(measure_cli.main, ["run", "--store", store_dir, "--full"])
    t_run = time.perf_counter() - t0
    key = re.search(r"measurement ([0-9a-f]{20})", out).group(1)
    from repro.service import ArtifactStore

    run = MeasurementRun.from_payload(ArtifactStore(store_dir).get(key).payload)
    check(not run.interpret and run.backend == "tpu",
          f"measurement ran backend={run.backend} interpret={run.interpret}")
    say("kernels", f"measure.cli run --full: {len(run.records)} records in "
        f"{t_run:.1f}s on {run.device_kind} x{run.device_count}, interpret=False")
    for name, configs in default_grid(smoke=False).items():
        worst = 0.0
        for cfg in configs:
            x = jax.random.normal(jax.random.PRNGKey(0), tuple(cfg["shape"]), jnp.float32)
            got = run_tiled(name, x, cfg["steps"], cfg["tiles"], interpret=False)
            worst = max(worst, close(got, run_ref(name, x, cfg["steps"])))
        check(worst <= 1.0, f"tiled {name}: error {worst:.3g} x the tolerance")
        say("kernels", f"tiled {name}: {len(configs)} (shape, tile) configs compiled, "
            f"vs kernels/ref.py worst error {worst:.3g} x the tolerance "
            f"(rtol={KERNEL_RTOL}) OK")
    for name, shape in (("jacobi2d", (1024, 1024)), ("heat3d", (64, 64, 128))):
        x = jax.random.normal(jax.random.PRNGKey(1), shape, jnp.float32)
        got = ops.stencil_step(name, x, interpret=False)
        worst = close(got, run_ref(name, x, 1))
        check(worst <= 1.0, f"banded {name}: error {worst:.3g} x the tolerance")
        say("kernels", f"banded {name} {shape}: compiled step vs kernels/ref.py "
            f"worst error {worst:.3g} x the tolerance (rtol={KERNEL_RTOL}) OK")
    return key


def phase_fit(store_dir, measurement_key):
    from repro.measure import cli as measure_cli

    out = run_cli(measure_cli.main,
                  ["fit", "--store", store_dir, "--measurement", measurement_key])
    for line in out.strip().splitlines():
        say("fit", line.strip())
    cal_key = re.search(r"calibration ([0-9a-f]{20})", out).group(1)
    out = run_cli(measure_cli.main, ["build", "--store", store_dir,
                                     "--calibration", cal_key, "--engine", "jax"])
    check("calibrated sweep" in out, "no calibrated sweep was stored")
    say("fit", out.strip())


def phase_lm():
    import numpy as np

    import jax
    import jax.numpy as jnp

    from repro.configs.base import SHAPES, ShapeSpec
    from repro.core.lmcells import (_cell_consts, _grid_times, enumerate_lm_hw_space,
                                    lm_cell_roofline, lm_codesign, lm_sw_lattice, lm_workload)
    from repro.obs.trace import trace

    def per_cell_grids(wl, hw):
        """The grids as a per-cell loop evaluates them: one jit a cell,
        constants as Python floats, meshes (H, 1), lattice (L,)."""
        f32 = lambda a: jnp.asarray(np.asarray(a, np.float32))
        for cell in wl.cells:
            lat = lm_sw_lattice(cell.op)
            grid = jax.jit(lambda *a, op=cell.op: _grid_times(op, *a, jnp))(
                _cell_consts(cell), f32(hw.pod)[:, None], f32(hw.data)[:, None],
                f32(hw.model)[:, None], f32(lat.microbatches), f32(lat.remat_full),
                f32(lat.fsdp), f32(lat.compress))
            grid = np.asarray(grid, np.float64)
            j = np.argmin(grid, axis=1)
            t = grid[np.arange(len(hw)), j]
            yield t, np.where(np.isfinite(t), j, -1)

    deepseek = lm_workload(archs=["deepseek-v3-671b"], name="deepseek-v3", shapes={
        "prefill": SHAPES["prefill_32k"], "decode": SHAPES["decode_32k"],
        "train": ShapeSpec("train_3072x4k", 4096, 3072, "train")})
    for name, wl, max_chips in (("default pair", lm_workload(), 512),
                                ("deepseek-v3", deepseek, 2048)):
        hw = enumerate_lm_hw_space(max_chips=max_chips)
        t0 = time.perf_counter()
        res_jx = lm_codesign(wl, hw=hw, engine="jax")
        t_jx = time.perf_counter() - t0
        res_np = lm_codesign(wl, hw=hw, engine="numpy")
        feas = np.isfinite(res_np.cell_time)
        check(np.array_equal(feas, np.isfinite(res_jx.cell_time)), f"LM {name} feasibility differs")
        rel = np.abs(res_jx.cell_time[feas] - res_np.cell_time[feas]) / res_np.cell_time[feas]
        check(rel.max(initial=0.0) <= RTOL, f"LM {name} max rel time err {rel.max():.3g}")
        diffs = 0
        for ci, cell in enumerate(wl.cells):
            lat = lm_sw_lattice(cell.op)
            for hi in np.nonzero(feas[ci] & (res_jx.cell_plan_idx[ci] != res_np.cell_plan_idx[ci]))[0]:
                p = res_np.hw.point(int(hi))
                r = lm_cell_roofline(cell, lat.plan(p["pod"], p["data"], p["model"],
                                                    int(res_jx.cell_plan_idx[ci, hi])))
                check(r["feasible"] and abs(r["bound_s"] - res_np.cell_time[ci, hi])
                      <= RTOL * res_np.cell_time[ci, hi], f"LM {cell.label} hw {hi} not tied")
                diffs += 1
        best, gflops = res_np.best(max_chips)
        check(np.isfinite(gflops) and gflops > 0 and feas[:, best].all(),
              f"LM {name}: no design feasible for every cell within {max_chips} chips")
        ref_t, ref_i = (np.array(a) for a in zip(*per_cell_grids(wl, hw)))
        check(res_jx.cell_time.tobytes() == ref_t.tobytes()
              and res_jx.cell_plan_idx.tobytes() == ref_i.tobytes(),
              f"LM {name}: jax differs from one jitted grid a cell")
        # a repeat of the question: one lm.dispatch per cell, none
        # compiling, then one lm.fetch of every grid
        C = len(wl.cells)
        with trace("lm") as root:
            lm_codesign(wl, hw=hw, engine="jax")
        (top,) = root.tree()["children"]
        dispatches, fetch = top["children"][:C], top["children"][C:]
        check(top["name"] == "lm.codesign" and top["attrs"]["transfers"] == 1 + C
              and [d["name"] for d in dispatches] == ["lm.dispatch"] * C
              and not any(d.get("children") for d in dispatches)
              and [(f["name"], f["attrs"]) for f in fetch] == [("lm.fetch", {"grids": C})],
              f"LM {name}: spans {top}")
        compiles = [d["attrs"]["compiles"] for d in dispatches]
        check(compiles == [0] * C, f"LM {name}: repeat compiled {compiles}")
        say("lm", f"{name}: lm_codesign jax vs numpy: {C} cells x {len(res_np.hw)} "
            f"meshes, feasibility exact, max rel err {rel.max(initial=0.0):.3g}, "
            f"{diffs} differing plans all tied; best {res_np.hw.point(best)} at "
            f"{gflops:.6g} GFLOP/s; bit-identical to per-cell grids; repeat {C} dispatches, "
            f"compiles {compiles}, {top['attrs']['transfers']} transfers, one fetch; "
            f"jax {t_jx:.2f}s OK")


def phase_portfolio(oracles):
    import numpy as np

    from repro.core.portfolio import optimize_portfolio_arrays

    res = oracles["gtx980"][0]
    cols = np.arange(0, len(res.hw), PORTFOLIO_STRIDE)
    area = np.asarray(res.hw.area)[cols]
    budget = 3.0 * float(np.median(area))
    for objective in ("density", "throughput"):
        r = {
            eng: optimize_portfolio_arrays(
                area, res.cell_time[:, cols], res.cell_flops(), res.cell_freqs(),
                3, budget, objective=objective, engine=eng,
            )
            for eng in ("numpy", "jax")
        }
        check(r["jax"].members == r["numpy"].members,
              f"portfolio {objective}: jax {r['jax'].members} != numpy {r['numpy'].members}")
        say("portfolio", f"K=3 {objective} over gtx980 oracle matrix "
            f"({len(cols)} hw, budget {budget:.1f} mm^2): jax members "
            f"{list(r['jax'].members)} == numpy OK")


def phase_sharded(n_dev):
    """The sharded engine over n_dev chips against engine="jax" on one:
    ``engine="auto"`` when n_dev is every attached chip, else
    ``sweep_cells_sharded(devices=n_dev)`` one stencil group at a time."""
    import jax
    import numpy as np

    from repro.core import codesign, enumerate_hw_space, sweep
    from repro.core.codesign import _stencil_groups
    from repro.core.engines import dispatch_engine
    from repro.core.workload import paper_workload

    wl = paper_workload()
    full = enumerate_hw_space()
    for label, hw in (("paper space", full), ("every third point", full.downsample(3))):
        res_jax = codesign(wl, hw=hw, engine="jax")
        t0 = time.perf_counter()
        if n_dev == jax.device_count():
            check(dispatch_engine("auto", len(hw)) == "sharded",
                  f"engine=auto does not shard over {n_dev} chips")
            res = codesign(wl, hw=hw, engine="auto")
            times, idx, how = res.cell_time, res.cell_tile_idx, "engine=auto"
        else:
            times = np.empty_like(res_jax.cell_time)
            idx = np.empty_like(res_jax.cell_tile_idx)
            for st, cis, sizes in _stencil_groups(wl).values():
                times[cis], idx[cis] = sweep.sweep_cells_sharded(
                    st, res_jax.gpu, sizes, hw.n_sm, hw.n_v, hw.m_sm,
                    res_jax.lattices[cis[0]], devices=n_dev)
            how = "sweep_cells_sharded"
        t_sh = time.perf_counter() - t0
        same = (np.array_equal(times, res_jax.cell_time)
                and np.array_equal(idx, res_jax.cell_tile_idx))
        check(same, f"sharded over {n_dev} chips differs from jax on {label}")
        say("sharded", f"{label} (H={len(hw)}): {how} on {n_dev} chips "
            f"bit-identical to engine=jax on one (cell_time and cell_tile_idx); "
            f"sharded {t_sh:.2f}s OK")


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded sweep across four chips")
    ap.add_argument("--out", default=tempfile.gettempdir(),
                    help="directory for this run's stores (a fresh one inside, "
                         "removed at the end)")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repro sources at {SRC}; run from a checkout",
              file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    if jax.devices()[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (jax backend {jax.default_backend()!r})",
              file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(jax.devices())} attached",
              file=sys.stderr)
        return 2

    t_all = time.perf_counter()
    device = phase_device(jax)
    os.makedirs(args.out, exist_ok=True)
    run_dir = tempfile.mkdtemp(prefix="chip_smoke-", dir=args.out)
    try:
        if args.chips == 4:
            phases = [("sharded", lambda: phase_sharded(4))]
        else:
            store_dir = os.path.join(run_dir, "store")
            oracle_dir = os.path.join(run_dir, "oracle")
            state = {}

            def sweep():
                state["store"], state["arts"], state["oracles"] = phase_sweep(
                    store_dir, oracle_dir)

            def kernels():
                state["measurement"] = phase_kernels(store_dir, jax)

            phases = [
                ("sweep", sweep),
                ("serve", lambda: phase_serve(store_dir, state["store"],
                                              state["arts"], state["oracles"])),
                ("kernels", kernels),
                ("fit", lambda: phase_fit(store_dir, state["measurement"])),
                ("lm", phase_lm),
                ("portfolio", lambda: phase_portfolio(state["oracles"])),
            ]
        for name, fn in phases:
            t0 = time.perf_counter()
            try:
                fn()
            except PhaseFailed as e:
                say(name, f"FAILED: {e}")
                return 1
            say(name, f"phase done in {time.perf_counter() - t0:.1f}s")
    finally:
        shutil.rmtree(run_dir, ignore_errors=True)
    say("total", f"{time.perf_counter() - t_all:.1f}s")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
