#!/usr/bin/env python
"""CI chaos lane for the gateway's resilience layer: real processes, real
sockets, real injected faults.

Each scenario starts ``python -m repro.service.cli serve`` as a child
armed via the ``REPRO_FAULTS`` env var (:mod:`repro.service.faults`) and
asserts three things: the failure is **structured** (documented wire code
+ HTTP status, never a hung connection or a traceback), responses are
**never corrupted** (success bytes stay byte-identical to an in-process
oracle over the same artifact), and the stack **recovers** once the
fault clears (faults are count-limited, so the harness can outlive them).

1. slow store + deadline: ``store.open`` latency makes a 100ms-budget
   request answer 504 ``deadline_exceeded``; the next (fault-free,
   budget-free) request is byte-identical to the oracle;
2. failing store + circuit breaker: two injected ``store.open`` errors
   answer 500 ``internal`` and open the breaker (threshold 2); the next
   request fails fast as 503 ``circuit_open`` + Retry-After WITHOUT
   touching the store; after the cooldown a half-open probe recovers and
   answers byte-identically;
3. dropped sockets + client retries: the handler abandons two
   connections mid-request; the stock ``GatewayClient`` retry policy
   resends (connection reset = provably-unexecuted) and the caller sees
   one transparent, byte-identical success;
4. held build lock: with another process owning the build flock and
   ``REPRO_LOCK_TIMEOUT_S=1``, ``cli build`` exits 2 with a one-line
   ``build_lock_timeout`` error -- no traceback, no hang;
5. rate limiting: ``serve --client-rate-limit`` answers 429
   ``rate_limited`` + Retry-After once the bucket drains, and a client
   honoring the hint succeeds on retry.

Exit 0 and print PASS only if every check holds.

This is a CPU lane. The parent starts the CLIs as child processes and
starts no JAX backend of its own before they run: a parent that held an
accelerator would lock its children out of it. On a TPU host, run
``python chip_smoke.py`` instead, which drives the same paths in one
process.

Usage: python scripts/chaos_smoke.py [--store DIR] [--downsample N]
"""

from __future__ import annotations

import argparse
import json
import os
import re
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request

# runnable with or without `pip install -e .` (CI installs; dev may not)
_SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
if os.path.isdir(_SRC) and _SRC not in sys.path:
    sys.path.insert(0, _SRC)

from repro.service import (  # noqa: E402
    ArtifactStore,
    CodesignServer,
    GatewayClient,
    RetryPolicy,
    wire,
)
from repro.service.query import QueryRequest  # noqa: E402

try:
    import fcntl  # noqa: E402
except ImportError:  # pragma: no cover - non-POSIX
    fcntl = None

CLI = [sys.executable, "-m", "repro.service.cli"]
GPU = "gtx980"


def _env(**extra: str) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    env.update(extra)
    return env


def check(ok: bool, what: str) -> None:
    print(f"  {'ok' if ok else 'FAIL'}: {what}")
    if not ok:
        raise SystemExit(f"chaos smoke failed at: {what}")


class Serve:
    """One `cli serve` child with faults/flags; context-managed teardown."""

    def __init__(self, store_root: str, *flags: str, faults_spec=None):
        env = _env()
        if faults_spec:
            env["REPRO_FAULTS"] = json.dumps(faults_spec)
        self.proc = subprocess.Popen(
            CLI + ["serve", "--store", store_root, "--port", "0", *flags],
            stdout=subprocess.PIPE, text=True, env=env,
        )
        self.url = None
        for line in self.proc.stdout:  # the bound port is printed last
            m = re.search(r"serving on (http://\S+)", line)
            if m:
                self.url = m.group(1)
                break
        check(self.url is not None, "serve printed its bound address")

    def __enter__(self) -> "Serve":
        return self

    def __exit__(self, *exc) -> None:
        self.proc.terminate()
        self.proc.wait(timeout=30)


def post(url: str, body: bytes, path: str = "/v1/query", headers=None):
    """(status, headers, body) for one POST; HTTP errors return, not raise."""
    req = urllib.request.Request(
        url + path, data=body, method="POST",
        headers={"Content-Type": "application/json", **(headers or {})},
    )
    try:
        with urllib.request.urlopen(req, timeout=30) as r:
            return r.status, dict(r.headers), r.read()
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), e.read()


def assert_coded(status, body, code: str, what: str) -> None:
    payload = json.loads(body)
    check(
        status == wire.ERROR_HTTP_STATUS[code]
        and payload.get("ok") is False
        and payload["error"]["code"] == code
        and bool(payload["error"]["message"]),
        what,
    )


def scrape(url: str) -> dict:
    with urllib.request.urlopen(url + "/v1/metrics?format=json", timeout=30) as r:
        return json.loads(r.read())


def total(snap: dict, name: str) -> float:
    metric = snap.get(name)
    if not metric:
        return 0.0
    return sum(s["value"] for s in metric["samples"])


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--store", default=None, help="store dir (default: temp)")
    ap.add_argument("--downsample", type=int, default=48,
                    help="hw-space thinning for the tiny build")
    args = ap.parse_args()
    store_root = args.store or tempfile.mkdtemp(prefix="chaos-smoke-")

    print(f"[1/6] building one artifact under {store_root}")
    subprocess.run(
        CLI + ["build", "--store", store_root, "--gpu", GPU,
               "--engine", "numpy", "--downsample", str(args.downsample)],
        check=True, env=_env(), timeout=600,
    )
    store = ArtifactStore(store_root)
    key = store.keys()[0]
    oracle = CodesignServer.from_artifact(store, store.get(key), batch_window=0.0)
    req = QueryRequest(freqs={"heat2d": 2.0, "jacobi2d": 1.0},
                       max_area=500.0, top_k=3, use_cache=False)
    want = wire.encode_response(oracle.query(req))
    body = wire.encode_request(req, artifact=key)

    print("[2/6] slow store + deadline -> 504, then clean recovery")
    with Serve(store_root,
               faults_spec={"store.open": {"latency_s": 0.5, "count": 1}}) as s:
        status, _, raw = post(
            s.url, body, headers={"X-Repro-Deadline-Ms": "100"}
        )
        assert_coded(status, raw, "deadline_exceeded",
                     "100ms budget vs 500ms store latency -> 504 deadline_exceeded")
        snap = scrape(s.url)
        check(total(snap, "repro_resilience_deadline_exceeded_total") >= 1,
              "deadline metric counted the hit")
        check(total(snap, "repro_faults_fired_total") == 1,
              "exactly one injected fault fired")
        status, _, raw = post(s.url, body)
        check(status == 200 and raw == want,
              "fault cleared: answer byte-identical to the in-process oracle")

    print("[3/6] failing store -> breaker opens -> fail-fast -> probe recovers")
    with Serve(store_root, "--breaker-threshold", "2",
               "--breaker-cooldown", "1",
               faults_spec={"store.open":
                            {"error": "OSError:injected disk failure",
                             "count": 2}}) as s:
        for i in (1, 2):
            status, _, raw = post(s.url, body)
            assert_coded(status, raw, "internal",
                         f"raw store failure {i} -> 500 internal")
        status, headers, raw = post(s.url, body)
        assert_coded(status, raw, "circuit_open",
                     "threshold reached -> 503 circuit_open (fail-fast)")
        check(int(headers.get("Retry-After", 0)) >= 1,
              "circuit_open carries Retry-After")
        snap = scrape(s.url)
        check(total(snap, "repro_resilience_breaker_transitions_total") >= 1,
              "breaker transition metric recorded")
        time.sleep(1.2)  # cooldown: the next request is the half-open probe
        status, _, raw = post(s.url, body)
        check(status == 200 and raw == want,
              "half-open probe recovers, byte-identical answer")

    print("[4/6] dropped sockets -> client retry policy recovers transparently")
    with Serve(store_root,
               faults_spec={"gateway.drop_socket": {"count": 2}}) as s:
        client = GatewayClient(
            s.url, retry=RetryPolicy(max_retries=3, base_s=0.05)
        )
        raw = client.query_bytes(req, artifact=key)
        check(raw == want,
              "two dropped connections -> retried, byte-identical answer")
        check(client.stats["retries"] == 2,
              f"client counted 2 retries (got {client.stats['retries']})")
        snap = scrape(s.url)
        check(total(snap, "repro_faults_fired_total") == 2,
              "both socket drops fired")

    print("[5/6] held build lock -> cli build exits 2 with build_lock_timeout")
    if fcntl is None:
        print("  skip: no fcntl on this platform")
    else:
        from repro.core.timemodel import GPUS_BY_NAME

        lock_root = tempfile.mkdtemp(prefix="chaos-lock-")
        # the key `cli build` will want, computed without building (the
        # spec is content-addressed: same params -> same key)
        probe = CodesignServer(
            ArtifactStore(lock_root), gpu=GPUS_BY_NAME[GPU],
            downsample=args.downsample, engine="numpy", batch_window=0.0,
        )
        lock_path = os.path.join(lock_root, f".lock-{probe.key}")
        fd = os.open(lock_path, os.O_CREAT | os.O_RDWR, 0o644)
        fcntl.flock(fd, fcntl.LOCK_EX)
        try:
            r = subprocess.run(
                CLI + ["build", "--store", lock_root, "--gpu", GPU,
                       "--engine", "numpy",
                       "--downsample", str(args.downsample)],
                capture_output=True, text=True, timeout=120,
                env=_env(REPRO_LOCK_TIMEOUT_S="1"),
            )
            check(r.returncode == 2, "held lock -> exit 2")
            check("build_lock_timeout" in r.stderr
                  and "Traceback" not in r.stderr,
                  "one-line build_lock_timeout error, no traceback")
        finally:
            fcntl.flock(fd, fcntl.LOCK_UN)
            os.close(fd)

    print("[6/6] rate limit -> 429 + Retry-After; honoring it succeeds")
    with Serve(store_root, "--client-rate-limit", "1") as s:
        status, _, _ = post(s.url, body)
        check(status == 200, "first request rides the burst token")
        status, headers, raw = post(s.url, body)
        assert_coded(status, raw, "rate_limited",
                     "drained bucket -> 429 rate_limited")
        retry_after = int(headers.get("Retry-After", 0))
        check(retry_after >= 1, "429 carries Retry-After")
        client = GatewayClient(s.url, retry=RetryPolicy(max_retries=3))
        raw = client.query_bytes(req, artifact=key)
        check(raw == want and client.stats["retries"] >= 1,
              "client honored Retry-After and recovered byte-identically")
        snap = scrape(s.url)
        check(total(snap, "repro_resilience_rejections_total") >= 2,
              "rejection metrics counted both 429s")

    print("PASS: chaos smoke (deadlines + breaker + retries + lock timeout "
          "+ rate limit; zero corrupted responses)")


if __name__ == "__main__":
    main()
