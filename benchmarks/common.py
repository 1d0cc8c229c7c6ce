"""Shared benchmark utilities: timing + CSV emission + artifact cache +
the --smoke contract (tiny problem sizes / downsampled hardware spaces so
the whole suite is CI-runnable in minutes)."""

from __future__ import annotations

import json
import os
import time
from typing import Callable, Dict

ARTIFACTS = os.path.join(os.path.dirname(__file__), "artifacts")

#: hardware-space downsampling stride used by suites in smoke mode.
SMOKE_HW_STRIDE = 8

#: the paper's two Fig.-3 workload classes -- single source of truth for
#: every suite that reproduces or cross-checks the Fig.-3 sweep.
STENCIL_CLASSES = {
    "2d": ["jacobi2d", "heat2d", "laplacian2d", "gradient2d"],
    "3d": ["heat3d", "laplacian3d"],
}


def smoke() -> bool:
    """True when running under ``benchmarks/run.py --smoke`` (env contract
    so suite modules stay import-order independent)."""
    return os.environ.get("REPRO_BENCH_SMOKE", "") == "1"


def skey(key: str) -> str:
    """Artifact cache key, segregated per mode so smoke runs never poison
    (or read) the full-fidelity cache."""
    return key + ("_smoke" if smoke() else "")


def timed(fn: Callable, *args, repeats: int = 3, **kw):
    """(result, best microseconds per call)."""
    best = float("inf")
    out = None
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        best = min(best, (time.perf_counter() - t0) * 1e6)
    return out, best


def emit(name: str, us_per_call: float, derived: str) -> None:
    """The harness CSV contract: name,us_per_call,derived."""
    print(f"{name},{us_per_call:.1f},{derived}")


def cache_json(key: str, compute: Callable[[], Dict], force: bool = False) -> Dict:
    os.makedirs(ARTIFACTS, exist_ok=True)
    path = os.path.join(ARTIFACTS, key + ".json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)
    out = compute()
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    return out
