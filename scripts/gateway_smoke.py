#!/usr/bin/env python
"""CI smoke lane for the fleet gateway: real processes, real sockets.

End-to-end, through the actual CLI entry points (no test fixtures):

1. build two tiny artifacts into one store -- same workload, two GPU
   targets (gtx980 + titanx), so routing has a genuine choice to make;
2. start ``python -m repro.service.cli serve`` as a child process and
   read the bound port off its stdout;
3. for each GPU: query over HTTP and assert the raw response bytes are
   **byte-identical** to the in-process ``CodesignServer`` oracle for the
   same artifact + request (the acceptance criterion), and that the
   response routed to the correct artifact key;
4. scrape ``GET /v1/metrics`` and assert the observability layer counted
   exactly the traffic issued: the ``/v1/query`` request counter matches
   the byte-identity step's query count, per-artifact hit counters and
   ``/v1/artifacts`` advisory ``hits``/``last_access`` rows agree, and
   the Prometheus text exposition parses line by line;
5. scrape ``GET /v1/slo`` and assert the ``/v1/query`` objective block
   carries 5m/1h windows with finite burn rates, a count equal to the
   queries issued, a legal status, and that ``/v1/healthz`` surfaces the
   same worst-route status in its ``slo`` field;
6. assert the structured error paths answer as documented
   (unknown artifact -> 404 ``unknown_artifact``, malformed JSON -> 400
   ``bad_request``) without taking the server down;
7. assert ``serve`` on a missing store exits non-zero with a one-line
   error (no traceback).

Exit 0 and print PASS only if every check holds.

This is a CPU lane. The parent starts the CLIs as child processes and
starts no JAX backend of its own before they run: a parent that held an
accelerator would lock its children out of it. On a TPU host, run
``python chip_smoke.py`` instead, which drives the same paths in one
process.

Usage: python scripts/gateway_smoke.py [--store DIR] [--downsample N]
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

from repro.service import ArtifactStore, CodesignServer, GatewayClient  # noqa: E402
from repro.service import wire  # noqa: E402
from repro.service.query import QueryRequest  # noqa: E402

CLI = [sys.executable, "-m", "repro.service.cli"]
GPUS = ("gtx980", "titanx")


def _env() -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    return env


def check(ok: bool, what: str) -> None:
    print(f"  {'ok' if ok else 'FAIL'}: {what}")
    if not ok:
        raise SystemExit(f"gateway smoke failed at: {what}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--store", default=None, help="store dir (default: temp)")
    ap.add_argument("--downsample", type=int, default=48,
                    help="hw-space thinning for the tiny builds")
    args = ap.parse_args()
    store_root = args.store or tempfile.mkdtemp(prefix="gateway-smoke-")

    print(f"[1/7] building {len(GPUS)} artifacts under {store_root}")
    for gpu in GPUS:
        subprocess.run(
            CLI + ["build", "--store", store_root, "--gpu", gpu,
                   "--engine", "numpy", "--downsample", str(args.downsample)],
            check=True, env=_env(), timeout=600,
        )

    # in-process oracles over the SAME stored artifacts (warm; never sweep)
    store = ArtifactStore(store_root)
    oracles = {}
    for row in store.entries():
        art = store.get(row["key"])
        oracles[row["gpu"]] = CodesignServer.from_artifact(store, art, batch_window=0.0)
    check(set(oracles) == set(GPUS), f"store holds one artifact per GPU {GPUS}")

    print("[2/7] starting the gateway (CLI serve, port 0)")
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
        check(client.health()["artifacts"] == len(GPUS), "healthz sees both artifacts")

        print(f"[3/7] HTTP vs in-process oracle at {url}")
        requests = [
            QueryRequest(freqs={"heat2d": 3.0, "jacobi2d": 1.0}, max_area=450.0,
                         top_k=3, use_cache=False),
            QueryRequest(freqs={"heat3d": 1.0}, pareto=True, fix={"n_sm": 16.0},
                         use_cache=False),
            QueryRequest(max_area=1.0, use_cache=False),  # infeasible: -inf
        ]
        for gpu, oracle in oracles.items():
            for req in requests:
                raw = client.query_bytes(req, route={"gpu": gpu})
                want = wire.encode_response(oracle.query(req))
                check(raw == want, f"byte-identical answer (gpu={gpu})")
                resp = wire.decode_response(raw)
                check(resp.artifact_key == oracle.key,
                      f"routed to the {gpu} artifact")

        print("[4/7] metrics scrape agrees with the traffic issued")
        n_queries = len(oracles) * len(requests)
        snap = client.metrics()  # canonical-JSON snapshot
        got = sum(s["value"]
                  for s in snap["repro_gateway_requests_total"]["samples"]
                  if s["labels"].get("route") == "/v1/query")
        check(got == n_queries,
              f"/v1/query request counter == {n_queries} queries issued")
        per_art = {s["labels"]["artifact"]: s["value"]
                   for s in snap["repro_gateway_artifact_requests_total"]["samples"]}
        check(all(per_art.get(o.key) == len(requests) for o in oracles.values()),
              f"per-artifact hit counters == {len(requests)} each")
        text = client.metrics("prometheus")
        sample_re = re.compile(r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? -?[0-9.e+-]+$')
        lines = [ln for ln in text.splitlines() if ln and not ln.startswith("#")]
        check(bool(lines) and all(sample_re.match(ln) for ln in lines),
              "prometheus text exposition parses line by line")
        check("# TYPE repro_gateway_requests_total counter" in text,
              "prometheus text carries TYPE metadata")
        rows = {r["key"]: r for r in client.artifacts()}
        check(all(rows[o.key]["hits"] == len(requests)
                  and rows[o.key]["last_access"] is not None
                  for o in oracles.values()),
              "/v1/artifacts rows carry matching hits + last_access")

        print("[5/7] /v1/slo scrape: objectives + burn rates over the traffic")
        import math
        slo = client.slo()
        q = slo["routes"].get("/v1/query")
        check(q is not None, "/v1/slo reports the /v1/query route")
        check(set(q["windows"]) == {"5m", "1h"}, "slo windows are 5m + 1h")
        check(all(math.isfinite(w["availability_burn"])
                  and math.isfinite(w["latency_burn"])
                  for w in q["windows"].values()),
              "burn rates are finite numbers")
        check(q["windows"]["1h"]["count"] == n_queries,
              f"slo 1h window counted the {n_queries} queries issued")
        check(q["status"] in ("ok", "burning", "violated"),
              "route status is a legal value")
        check(client.health()["slo"] in ("ok", "burning", "violated"),
              "healthz carries the fleet slo status")
        prom = client.slo("prometheus")
        check("repro_slo_burn_rate" in prom,
              "prometheus rendering exposes repro_slo_burn_rate")

        print("[6/7] structured error paths")
        try:
            client.query(requests[0], artifact="0" * 20)
            check(False, "unknown artifact must raise")
        except wire.RemoteError as e:
            check(e.code == "unknown_artifact" and e.http_status == 404,
                  "unknown artifact -> 404 unknown_artifact")
        bad = client._http("/v1/query", b"{not json")
        try:
            wire.decode_response(bad, client._last_status)
            check(False, "malformed JSON must raise")
        except wire.RemoteError as e:
            check(e.code == "bad_request" and client._last_status == 400,
                  "malformed JSON -> 400 bad_request")
        check(client.health()["ok"], "gateway still healthy after errors")
    finally:
        proc.terminate()
        proc.wait(timeout=30)

    print("[7/7] serve on a missing store exits cleanly")
    r = subprocess.run(
        CLI + ["serve", "--store", os.path.join(store_root, "nope"), "--port", "0"],
        capture_output=True, text=True, env=_env(), timeout=120,
    )
    check(r.returncode == 2 and "error:" in r.stderr and "Traceback" not in r.stderr,
          "missing store -> exit 2, one-line error, no traceback")

    print("PASS: gateway smoke (routing + HTTP transport + metrics + error paths)")


if __name__ == "__main__":
    main()
