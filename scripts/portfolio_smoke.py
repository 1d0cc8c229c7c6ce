#!/usr/bin/env python
"""CI smoke lane for portfolio codesign + routing: real processes/sockets.

End-to-end, through the actual CLI entry points (no test fixtures):

1. build two tiny sweep artifacts (gtx980 + titanx) into one store, then
   a K=2 throughput portfolio over each via ``cli portfolio``;
2. assert each portfolio's persisted fleet objective is >= the best
   single design the same sweep offers under the same budget (the
   "a fleet never loses to one chip" acceptance bound), and that
   rebuilding is a no-op landing on the identical content key;
3. start ``python -m repro.service.cli serve`` as a child process and,
   for every cell group of every portfolio, assert the raw ``/v1/route``
   response bytes over HTTP are **byte-identical** to the in-process
   ``PortfolioServer`` oracle (the acceptance criterion);
4. assert the structured route error paths answer as documented
   (unknown cell -> 404 ``unknown_cell``, a sweep key pinned on
   ``/v1/route`` -> ``wrong_artifact_kind``) without downing the server.

Exit 0 and print PASS only if every check holds.

This is a CPU lane. The parent starts the CLIs as child processes and
starts no JAX backend of its own before they run: a parent that held an
accelerator would lock its children out of it. On a TPU host, run
``python chip_smoke.py`` instead, which drives the same paths in one
process.

Usage: python scripts/portfolio_smoke.py [--store DIR] [--downsample N]
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

import numpy as np  # noqa: E402

from repro.service import ArtifactStore, GatewayClient, wire  # noqa: E402
from repro.service.portfolio import PortfolioServer, RouteRequest  # noqa: E402

CLI = [sys.executable, "-m", "repro.service.cli"]
GPUS = ("gtx980", "titanx")
BUDGET = 900.0


def _env() -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    return env


def check(ok: bool, what: str) -> None:
    print(f"  {'ok' if ok else 'FAIL'}: {what}")
    if not ok:
        raise SystemExit(f"portfolio smoke failed at: {what}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--store", default=None, help="store dir (default: temp)")
    ap.add_argument("--downsample", type=int, default=48,
                    help="hw-space thinning for the tiny builds")
    args = ap.parse_args()
    store_root = args.store or tempfile.mkdtemp(prefix="portfolio-smoke-")

    print(f"[1/4] building {len(GPUS)} sweeps + portfolios under {store_root}")
    for gpu in GPUS:
        base = ["--store", store_root, "--gpu", gpu, "--engine", "numpy",
                "--downsample", str(args.downsample)]
        subprocess.run(CLI + ["build"] + base, check=True, env=_env(), timeout=600)
        r = subprocess.run(
            CLI + ["portfolio"] + base
            + ["--k", "2", "--budget", str(BUDGET), "--objective", "throughput"],
            check=True, env=_env(), timeout=600, capture_output=True, text=True,
        )
        check(re.search(r"^portfolio [0-9a-f]{20}: built", r.stdout, re.M)
              is not None, f"cli portfolio built one manifest (gpu={gpu})")
        # deterministic: the second build must land on the same key, stored
        r2 = subprocess.run(
            CLI + ["portfolio"] + base
            + ["--k", "2", "--budget", str(BUDGET), "--objective", "throughput"],
            check=True, env=_env(), timeout=600, capture_output=True, text=True,
        )
        key = re.search(r"^portfolio ([0-9a-f]{20}):", r.stdout, re.M).group(1)
        check(f"portfolio {key}: already stored" in r2.stdout,
              f"rebuild is a stored no-op on the same content key (gpu={gpu})")

    print("[2/4] fleet objective >= best single design, per portfolio")
    store = ArtifactStore(store_root)
    oracles = {}  # gpu -> (PortfolioServer, portfolio key)
    for row in store.entries():
        if row.get("kind") != "portfolio":
            continue
        art = store.get(row["key"])
        sweep = store.get(art.payload["sweep_key"])
        gpu = row["gpu"]
        oracles[gpu] = PortfolioServer(art, sweep)
        # the eq.-18 single-design reduction, straight off the sweep arrays
        freqs = sweep.cell_freqs()
        wt = freqs @ np.asarray(sweep.cell_time, np.float64)
        g = (freqs @ sweep.cell_flops()) / wt / 1.0e9
        best_single = float(np.max(np.where(sweep.hw_area <= BUDGET, g, -np.inf)))
        fleet = float(art.payload["fleet_gflops"])
        check(fleet >= best_single * (1 - 1e-12),
              f"fleet {fleet:.1f} >= single {best_single:.1f} GFLOP/s (gpu={gpu})")
    check(set(oracles) == set(GPUS), f"store holds one portfolio per GPU {GPUS}")

    print("[3/4] starting the gateway; HTTP /v1/route vs in-process oracle")
    proc = subprocess.Popen(
        CLI + ["serve", "--store", store_root, "--port", "0"],
        stdout=subprocess.PIPE, text=True, env=_env(),
    )
    try:
        url = None
        for line in proc.stdout:  # the bound port is printed last
            m = re.search(r"serving on (http://\S+)", line)
            if m:
                url = m.group(1)
                break
        check(url is not None, "serve printed its bound address")
        client = GatewayClient(url)
        n = 0
        for gpu, oracle in oracles.items():
            for cell in oracle.cell_labels():
                req = RouteRequest(cell=cell)
                raw = client.route_bytes(req, route={"gpu": gpu})
                want = wire.encode_route_response(oracle.route(req))
                check(raw == want, f"byte-identical route (gpu={gpu} cell={cell})")
                resp = wire.decode_route_response(raw)
                check(not resp.degraded and resp.hw_index in oracle.members,
                      f"healthy answer from a member design ({gpu}/{cell})")
                n += 1
        check(n >= 2 * len(GPUS), f"routed {n} cell groups over HTTP")

        print("[4/4] structured route error paths")
        try:
            client.route("not-a-cell", route={"gpu": GPUS[0]})
            check(False, "unknown cell must raise")
        except wire.RemoteError as e:
            check(e.code == "unknown_cell" and e.http_status == 404,
                  "unknown cell -> 404 unknown_cell")
        sweep_key = oracles[GPUS[0]].sweep.key
        try:
            client.route("heat2d", artifact=sweep_key)
            check(False, "routing through a sweep key must raise")
        except wire.RemoteError as e:
            check(e.code == "wrong_artifact_kind",
                  "sweep key on /v1/route -> wrong_artifact_kind")
        check(client.health()["ok"], "gateway still healthy after errors")
    finally:
        proc.terminate()
        proc.wait(timeout=30)

    print("PASS: portfolio smoke (build + fleet bound + route byte-identity)")


if __name__ == "__main__":
    main()
