#!/usr/bin/env python
"""CI smoke lane for the predict -> measure -> refit -> serve loop.

End-to-end, through the actual CLI entry points (no test fixtures):

1. ``repro.measure.cli run --interpret``: execute the tile-parameterized
   Pallas stencils in interpret mode over the smoke measurement grid and
   persist the timings as a ``kind: "measurement"`` artifact;
2. ``repro.measure.cli fit --synthetic``: fit model-generated timings and
   assert the fit **recovers the generating machine parameters** (the
   calibration acceptance property) by reloading the stored calibration;
3. ``repro.measure.cli fit``: refit from the real harness run and assert
   the reported per-stencil error improved;
4. ``repro.measure.cli build``: solve a tiny sweep on the calibrated
   hardware and store it;
5. serve the store through the HTTP gateway and assert the calibrated
   artifact's answers are **byte-identical** to the in-process oracle,
   routed both by ``{"calibration": <key>}`` and by the calibrated GPU
   name -- and that measurement/calibration manifests in the same store
   neither route queries nor make sweep selectors ambiguous.

Exit 0 and print PASS only if every check holds.

This is a CPU lane. The parent starts the CLIs as child processes and
starts no JAX backend of its own before they run: a parent that held an
accelerator would lock its children out of it. On a TPU host, run
``python chip_smoke.py`` instead, which drives the same paths in one
process.

Usage: python scripts/measure_smoke.py [--store DIR] [--downsample N]
"""

from __future__ import annotations

import argparse
import os
import re
import subprocess
import sys
import tempfile

# runnable with or without `pip install -e .` (CI installs; dev may not)
_SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
if os.path.isdir(_SRC) and _SRC not in sys.path:
    sys.path.insert(0, _SRC)

from repro.core.timemodel import GPUSpec, StencilSpec  # noqa: E402
from repro.measure.calibrate import RECOVERY_RTOL, CalibrationResult  # noqa: E402
from repro.service import (  # noqa: E402
    ArtifactStore,
    CodesignServer,
    GatewayClient,
    wire,
)
from repro.service.query import QueryRequest  # noqa: E402

MEASURE_CLI = [sys.executable, "-m", "repro.measure.cli"]
SERVICE_CLI = [sys.executable, "-m", "repro.service.cli"]


def _env() -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    return env


def check(ok: bool, what: str) -> None:
    print(f"  {'ok' if ok else 'FAIL'}: {what}")
    if not ok:
        raise SystemExit(f"measure smoke failed at: {what}")


def _run(cmd, **kw):
    return subprocess.run(
        cmd, check=True, env=_env(), timeout=600, capture_output=True,
        text=True, **kw,
    )


def _key(stdout: str, kind: str) -> str:
    m = re.search(rf"{kind} ([0-9a-f]{{20}})", stdout)
    assert m, f"no {kind} key in output:\n{stdout}"
    return m.group(1)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--store", default=None, help="store dir (default: temp)")
    ap.add_argument("--downsample", type=int, default=48,
                    help="hw-space thinning for the calibrated build")
    args = ap.parse_args()
    root = args.store or tempfile.mkdtemp(prefix="measure-smoke-")

    print(f"[1/5] measurement run (Pallas interpret grid) under {root}")
    out = _run(
        MEASURE_CLI + ["run", "--store", root, "--repeats", "2", "--interpret"]
    ).stdout
    print(out, end="")
    meas_key = _key(out, "measurement")

    print("[2/5] synthetic fit recovers the generating machine")
    out = _run(
        MEASURE_CLI + ["fit", "--store", root, "--synthetic", "--perturb", "0.5"]
    ).stdout
    syn_key = _key(out, "calibration")
    store = ArtifactStore(root)
    syn_art = store.get(syn_key)
    syn = CalibrationResult.from_payload(syn_art.payload)
    # --synthetic generated timings from a machine 50% off the datasheet
    # start; the fit must travel back to it (the stored truth)
    truth = syn_art.manifest["extra"]["synthetic_truth"]
    truth_gpu = GPUSpec(**truth["gpu"])
    truth_st = {n: StencilSpec(**d) for n, d in truth["stencils"].items()}
    err = syn.param_rel_error(truth_gpu, truth_st)
    check(err < RECOVERY_RTOL,
          f"synthetic recovery rel err {err:.2e} < {RECOVERY_RTOL}")
    check(syn.loss_after < 1e-6, f"synthetic fit loss {syn.loss_after:.2e} ~ 0")

    print("[3/5] refit from the real harness timings improves the model")
    out = _run(
        MEASURE_CLI + ["fit", "--store", root, "--measurement", meas_key]
    ).stdout
    print(out, end="")
    cal_key = _key(out, "calibration")
    cal = CalibrationResult.from_payload(store.get(cal_key).payload)
    check(cal.loss_after < cal.loss_before, "refit reduced the fit loss")
    improved = sum(
        cal.errors_after[n] < cal.errors_before[n] for n in cal.errors_after
    )
    # per-stencil C_iter is a free parameter, so nearly every stencil must
    # improve; allow one holdout for shared-parameter (bw/launch) coupling
    # on a noisy runner
    check(improved >= len(cal.errors_after) - 1,
          f"per-stencil |rel err| improved for {improved}/{len(cal.errors_after)}")

    print("[4/5] calibrated sweep build")
    out = _run(
        MEASURE_CLI + ["build", "--store", root, "--calibration", cal_key,
                       "--downsample", str(args.downsample),
                       "--engine", "numpy"]
    ).stdout
    print(out, end="")
    sweep_key = _key(out, "calibrated sweep")
    oracle = CodesignServer.from_artifact(
        store, store.get(sweep_key), batch_window=0.0
    )

    print("[5/5] gateway serves the calibrated artifact byte-identically")
    proc = subprocess.Popen(
        SERVICE_CLI + ["serve", "--store", root, "--port", "0"],
        stdout=subprocess.PIPE, text=True, env=_env(),
    )
    try:
        url = None
        for line in proc.stdout:
            m = re.search(r"serving on (http://\S+)", line)
            if m:
                url = m.group(1)
                break
        check(url is not None, "serve printed its bound address")
        client = GatewayClient(url)
        rows = {r["key"]: r for r in client.artifacts()}
        check(rows[meas_key]["kind"] == "measurement"
              and rows[cal_key]["kind"] == "calibration"
              and rows[sweep_key]["kind"] == "sweep",
              "all three artifact kinds indexed")
        gpu_name = oracle.gpu.name
        requests = [
            QueryRequest(freqs={"heat2d": 2.0, "jacobi2d": 1.0},
                         max_area=450.0, top_k=3, use_cache=False),
            QueryRequest(pareto=True, fix={"n_sm": 16.0}, use_cache=False),
        ]
        for req in requests:
            want = wire.encode_response(oracle.query(req))
            by_cal = client.query_bytes(req, route={"calibration": cal_key})
            by_gpu = client.query_bytes(req, route={"gpu": gpu_name})
            check(by_cal == want,
                  f"byte-identical via calibration key (gpu={gpu_name})")
            check(by_gpu == want, f"byte-identical via gpu={gpu_name}")
        # batched endpoint: same two queries, one round trip, same bytes
        many = client.query_many(requests, route={"calibration": cal_key})
        check(
            all(r.artifact_key == sweep_key for r in many)
            and [r.best_index for r in many]
            == [wire.decode_response(
                    wire.encode_response(oracle.query(q))).best_index
                for q in requests],
            "query_many answers match per-query oracles",
        )
        # a calibration manifest must answer 400, not serve a query
        try:
            client.query(requests[0], artifact=cal_key)
            check(False, "querying a calibration manifest must fail")
        except wire.RemoteError as e:
            check(e.code == "wrong_artifact_kind" and e.http_status == 400,
                  "calibration manifest -> 400 wrong_artifact_kind")
    finally:
        proc.terminate()
        proc.wait(timeout=30)

    print("PASS: measure smoke (kernels + calibration + calibrated serving)")


if __name__ == "__main__":
    main()
