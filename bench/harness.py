"""The benchmark's general machinery, driven by ``BENCHMARK.json``.

A cell ``<config>.<traffic>`` is found by name: its configuration file is
the one ``BENCHMARK.json`` names, its traffic mix is
``traffic/<traffic>.json``, the loop that drives it is
``loops/<loop>.py`` where the mix names ``loop``, and each per-layer metric
``<name>`` is read by ``metrics/<name>.py``. Adding a configuration, a mix
or a metric adds files; nothing here names one.

One run: check the chips, set the loop up (that is ``setup_s``), measure
for ``--seconds`` (with ``--trace 1`` under the profiler), read the device
memory peak, let the loop check what the window produced against its
reference, and print the result as the last line of standard output.
"""

from __future__ import annotations

import argparse
import contextlib
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
#: JAX's persistent compilation cache: a fixed path inside the checkout, so
#: every run of a cell after the first loads its programs instead of
#: compiling them (the path is part of each entry's key).
CACHE_DIR = os.path.join(BENCH, ".jax_cache")


class Refused(Exception):
    """The run cannot measure this cell here; no result is printed."""


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.exists(path):
        raise Refused(f"no file {os.path.relpath(path, ROOT)}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, root: str = ROOT) -> Dict:
    """The cell's entry, its configuration, its traffic mix and the metrics
    it reports, from ``BENCHMARK.json`` and the files it names."""
    path = os.path.join(root, "BENCHMARK.json")
    if not os.path.exists(path):
        raise Refused(f"no BENCHMARK.json at {root}")
    with open(path) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json (have {sorted(cells)})")
    cell = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    with open(os.path.join(root, configs[cell["config"]]["file"])) as f:
        config = json.load(f)
    traffic_path = os.path.join(BENCH, "traffic", f"{cell['traffic']}.json")
    if not os.path.exists(traffic_path):
        raise Refused(f"no traffic mix {os.path.relpath(traffic_path, root)}")
    with open(traffic_path) as f:
        traffic = json.load(f)

    def mine(metric):
        return name in metric.get("workloads", [name])

    return {
        "cell": cell,
        "config": config,
        "traffic": traffic,
        "end_to_end": [m for m in spec["end_to_end"] if mine(m)],
        "per_layer": [m for m in spec["per_layer"] if mine(m)],
    }


def start_jax(chips: int, require_tpu: bool = True):
    """Import JAX with the benchmark's compile cache, and check that it
    sees exactly the cell's chips (the cell's path depends on the count)."""
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        raise Refused(f"no program sources at {os.path.join(ROOT, 'src')}")
    if ROOT + "/src" not in sys.path:
        sys.path.insert(0, os.path.join(ROOT, "src"))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    # the TPU runtime's logs go under this run's temp dir, not a fixed /tmp path
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(tempfile.gettempdir(), "tpu_logs"))
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise Refused(f"JAX finds no TPU (backend {devices[0].platform!r})")
    if require_tpu and len(devices) != chips:
        raise Refused(f"the cell asks for {chips} chips, JAX finds {len(devices)}")
    return jax, devices


class CompileCounter:
    """Counts the programs JAX compiles or loads from its cache."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, jax):
        self.n = 0
        self._monitoring = jax.monitoring
        self._monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, event, duration, **_):
        if event == self.EVENT:
            self.n += 1

    def close(self):
        self._monitoring.unregister_event_duration_listener(self._seen)


@contextlib.contextmanager
def profiled(jax, trace_dir: Optional[str]):
    """Run the body under the profiler when ``trace_dir`` is given."""
    if trace_dir is None:
        yield
        return
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # host spans only, not every Python call
    options.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks, default=0))


def finite(x: float) -> float:
    """JSON has no infinity: a comparison with no finite match prints 1e300."""
    return x if math.isfinite(x) else 1e300


def reduce_trace(trace_dir: str, per_layer: List[Dict]) -> Dict:
    """Per-layer metrics, ``busy_s``/``window_s`` and the breakdown of a
    traced window."""
    import trace_reduce

    trace = trace_reduce.load(trace_reduce.find_xplane(trace_dir))
    window = trace.window()
    if window is None:
        raise RuntimeError("the trace holds no bench.window span")
    lo, hi = window
    metrics = {}
    for m in per_layer:
        reader = load_module(os.path.join(BENCH, "metrics", f"{m['name']}.py"),
                             f"bench_metric_{m['name']}")
        value = reader.read(trace, lo, hi)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    devs = trace.devices
    busy = sum(trace.busy_in(d, lo, hi) for d in devs) / max(len(devs), 1)
    idle: Dict[str, float] = {}
    for d in devs:
        for name, ns in trace.idle_by_span(d, lo, hi).items():
            idle[name] = idle.get(name, 0.0) + ns / 1e9 / len(devs)
    return {
        "metrics": metrics,
        "busy_s": busy / 1e9,
        "window_s": (hi - lo) / 1e9,
        "breakdown": {
            "device_ops": [[n, ns / 1e9] for n, ns in trace.top_ops(lo, hi, 10, modules=True)],
            "idle_gaps": sorted(([n, s] for n, s in idle.items()),
                                key=lambda kv: -kv[1])[:10],
        },
    }


def run_cell(name: str, seed: int, seconds: float, trace: bool, t_start: float,
             require_tpu: bool = True, trace_dir: Optional[str] = None,
             say: Callable[[str], None] = lambda s: print(s, file=sys.stderr, flush=True),
             root: str = ROOT) -> Dict:
    """One run of one cell; returns the result object (the last line)."""
    found = load_cell(name, root)
    cell, traffic = found["cell"], found["traffic"]
    jax, devices = start_jax(cell["chips"], require_tpu)
    jax_s = time.perf_counter() - t_start
    loop_mod = load_module(os.path.join(BENCH, "loops", f"{traffic['loop']}.py"),
                           f"bench_loop_{traffic['loop']}")
    loop = loop_mod.Loop(found["config"], traffic, seed, say)
    counter = CompileCounter(jax)
    own_dir = trace and trace_dir is None
    if own_dir:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        loop.setup()
        setup_s = time.perf_counter() - t_start
        compiles = counter.n
        with profiled(jax, trace_dir if trace else None):
            window = loop.window(seconds)
        compiles = counter.n - compiles
        peak = memory_peak(devices)
        checks = loop.check()
        reduced = reduce_trace(trace_dir, found["per_layer"]) if trace else None
    finally:
        counter.close()
        loop.close()
        if own_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    say(f"set-up seconds: {jax_s:.3f} to JAX on its chips, {setup_s - jax_s:.3f} for the "
        f"inputs, store and warm-up question")
    say(f"window: {window['attempted']} questions in {window['window_s']:.3f} s, "
        f"{window['failed']} failed; {compiles} compilations inside the window")
    for line in window.get("notes", []):
        say(line)
    ok = window["failed"] == 0 and window["attempted"] > 0
    ok = ok and all(c["value"] <= c["limit"] for c in checks.values())
    result = {
        "correct": bool(ok),
        "attempted": window["attempted"],
        "failed": window["failed"],
        "metrics": {},
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": peak,
        },
    }
    if reduced is not None:
        result["metrics"] = reduced["metrics"]
        result["device"].update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        result["breakdown"] = reduced["breakdown"]
    else:
        measured = dict(window["metrics"], setup_s=setup_s)
        for m in found["end_to_end"]:
            result["metrics"][m["name"]] = {"value": measured[m["name"]], "unit": m["unit"]}
    result["checks"] = {k: {"value": finite(c["value"]), "limit": c["limit"]}
                        for k, c in checks.items()}
    for k, c in result["checks"].items():
        say(f"check {k}: {c['value']!r} (limit {c['limit']!r})")
    return result


def main(argv=None, t_start: Optional[float] = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True, help="cell name, <config>.<traffic>")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True, help="measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0,
                    help="1: per-layer metrics from a profiler trace of the window")
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler trace here instead of a removed temp dir")
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                          t_start, trace_dir=args.trace_dir)
    except Refused as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result))
    return 0
