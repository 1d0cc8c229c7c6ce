"""A closed loop of cold codesign questions, one at a time.

Each question is what a design-study user's ``cli query`` does on a store
that does not hold the key: construct a ``CodesignServer`` over the
configuration's workload, GPU constants, lattices and hardware points, with
``engine="auto"`` (the sharded engine when more than one chip is attached)
and no batching window, and ask it for the best design under the
configuration's area budget with the workload's own uniform mix. That runs
the server's build path, the sweep on the device, the store's staged write
and reload, and the query's reduction.

Every question gets the whole hardware space in a new order drawn from the
seed: identical work at identical shapes (nothing recompiles), but a new
content address, so no store or in-process memo can answer it. A question
counts as failed when it raises, answers no design, reuses an address, or
was not built by its server.

The benchmark's own host spans (``jax.profiler.TraceAnnotation``) mark each
question, the server's construction, the query, and inside it the
program's ``codesign()`` and ``ArtifactStore.put``; they are set here by
wrapping those two calls, not inside the program.

Each question's artifact is removed once it can no longer be checked.
Checked are ``check.questions`` questions drawn from the seed among the
window's first ``check.among`` (the window answers at least that many),
one drawn from the seed uniformly over the rest of the window (a reservoir
of one), and the window's last question, so a fault that grows with the
window's state is seen too. After the window those artifacts are read back
from the store and compared with ``oracle.py`` at a seeded sample of
hardware columns plus the columns the artifact itself ranks best.
"""

from __future__ import annotations

import gc
import os
import shutil
import tempfile
import time
import traceback
from typing import Callable, Dict, List, Optional

import numpy as np

import oracle


class Loop:
    def __init__(self, config: dict, traffic: dict, seed: int, say: Callable[[str], None]):
        self.cfg = config
        self.traffic = traffic
        self.seed = seed
        self.say = say
        self.budget = float(config["query"]["max_area"])
        self.records: List[Dict] = []
        self.keys = set()
        self.kept = set()
        self._parts: Dict[str, float] = {}
        self.store_root: Optional[str] = None
        self._restore: Optional[Callable[[], None]] = None

    # ---- set-up ------------------------------------------------------------
    def setup(self) -> None:
        """Build the inputs, the store and the span wrappers, and answer one
        warm-up question (it compiles or loads the cell's programs)."""
        import jax
        from repro.core.codesign import HardwareSpace
        from repro.core.solver import TileLattice
        from repro.core.timemodel import GPUSpec, ProblemSize, StencilSpec
        from repro.core.workload import Workload, WorkloadCell
        from repro.service import server as server_mod
        from repro.service.query import QueryRequest
        from repro.service.store import ArtifactStore

        cfg = self.cfg
        self.annotate = jax.profiler.TraceAnnotation
        self.HardwareSpace = HardwareSpace
        self.CodesignServer = server_mod.CodesignServer
        self.space = oracle.hardware_space(cfg)
        specs = {s["name"]: StencilSpec(**s) for s in cfg["stencils"]}
        self.workload = Workload(cfg["workload_name"], tuple(
            WorkloadCell(specs[c["stencil"]["name"]],
                         ProblemSize(s1=c["s1"], s2=c["s2"], t=c["t"], s3=c["s3"]),
                         c["freq"])
            for c in oracle.cells(cfg)
        ))
        self.gpu = GPUSpec(**cfg["gpu"])
        self.lattices = {d: TileLattice(**{k: tuple(v) for k, v in cfg["lattices"][d].items()})
                         for d in ("2d", "3d")}
        self.request = QueryRequest(max_area=self.budget)

        self.store_root = tempfile.mkdtemp(prefix="bench-store-")
        self.store = ArtifactStore(self.store_root)
        real_codesign, real_put = server_mod.codesign, self.store.put

        def timed(name, call):
            def wrapped(*args, **kwargs):
                t0 = time.perf_counter()
                try:
                    with self.annotate(f"bench.{name}"):
                        return call(*args, **kwargs)
                finally:
                    self._parts[name] = self._parts.get(name, 0.0) + time.perf_counter() - t0
            return wrapped

        codesign, put = timed("codesign", real_codesign), timed("store_put", real_put)

        server_mod.codesign = codesign
        self.store.put = put
        self._restore = lambda: setattr(server_mod, "codesign", real_codesign)

        warm = self.question(0)
        if not warm["ok"]:
            raise RuntimeError(f"warm-up question failed: {warm.get('error', warm)}")
        self.say(f"warm-up question: {warm['s']:.3f} s")
        self.store.delete(warm["key"])

    def question(self, q: int) -> Dict:
        hw = self.HardwareSpace(**oracle.permuted(self.space, self.seed, q))
        rec: Dict = {"q": q, "ok": False}
        self._parts = {}
        t0 = time.perf_counter()
        try:
            with self.annotate("bench.question"):
                with self.annotate("bench.server"):
                    srv = self.CodesignServer(
                        self.store, workload=self.workload, gpu=self.gpu, hw=hw,
                        engine="auto", lattice_2d=self.lattices["2d"],
                        lattice_3d=self.lattices["3d"], batch_window=0.0,
                    )
                t1 = time.perf_counter()
                with self.annotate("bench.query"):
                    resp = srv.query(self.request)
        except Exception:  # a failed question is counted, and the loop goes on
            rec["error"] = traceback.format_exc(limit=4)
            return rec
        rec["s"] = time.perf_counter() - t0
        rec["parts"] = dict(self._parts, server=t1 - t0,
                            query_rest=rec["s"] - (t1 - t0) - sum(self._parts.values()))
        fresh = srv.key not in self.keys
        self.keys.add(srv.key)
        rec.update(
            key=srv.key, fresh=fresh, built=srv.stats["artifact_builds"] == 1,
            best_index=int(resp.best_index), best_gflops=float(resp.best_gflops),
            best_point={k: float(v) for k, v in resp.best_point.items()},
        )
        rec["ok"] = fresh and rec["built"] and rec["best_index"] >= 0
        return rec

    # ---- the measured window ----------------------------------------------
    def window(self, seconds: float) -> Dict:
        """Questions back to back until ``seconds`` have passed (and at least
        as many as the check reads); the window ends when the last one is
        answered."""
        chk = self.traffic["check"]
        among = chk["among"]
        early = {int(q) for q in oracle.rng_for(self.seed, 3).choice(
            np.arange(1, among + 1), size=chk["questions"], replace=False)}
        draw = oracle.rng_for(self.seed, 4)
        late = None  # drawn uniformly from the questions after the first `among`
        on_disk: Dict[int, str] = {}
        collected = [0, 0.0]  # Python's garbage collections in the window: count, seconds

        def on_gc(phase, info):
            if phase == "start":
                collected.append(time.perf_counter())
            else:
                collected[0] += 1
                collected[1] += time.perf_counter() - collected.pop()

        q = 1
        gc.callbacks.append(on_gc)
        t0 = time.perf_counter()
        try:
            with self.annotate("bench.window"):
                while time.perf_counter() - t0 < seconds or q <= among:
                    rec = self.question(q)
                    self.records.append(rec)
                    if q > among and draw.random() * (q - among) < 1.0:
                        late = q
                    if "key" in rec:
                        on_disk[q] = rec["key"]
                    for old in [o for o in on_disk if o not in early and o not in (late, q)]:
                        self.store.delete(on_disk.pop(old))
                    q += 1
            span = time.perf_counter() - t0
        finally:
            gc.callbacks.remove(on_gc)
        self.kept = early | {q - 1} | ({late} if late is not None else set())
        n = len(self.records)
        failed = [r for r in self.records if not r["ok"]]
        notes = [
            f"cold: {n} questions, {sum(r.get('fresh', False) for r in self.records)} new "
            f"content addresses, {sum(r.get('built', False) for r in self.records)} "
            f"artifact builds",
        ]
        timed = [r for r in self.records if "s" in r]
        if timed:
            took = sorted(r["s"] for r in timed)
            slowest = max(timed, key=lambda r: r["s"])
            notes.append(f"question seconds: min {took[0]:.4f}, median {took[len(took) // 2]:.4f}, "
                         f"max {took[-1]:.4f}; in order {[round(r.get('s', -1), 4) for r in self.records[:12]]}")
            notes.append("median seconds by part: " + ", ".join(
                f"{k} {float(np.median([r['parts'][k] for r in timed])):.4f}" for k in slowest["parts"]))
            notes.append(f"slowest, question {slowest['q']}: "
                         + ", ".join(f"{k} {v:.4f}" for k, v in slowest["parts"].items()))
        notes.append(f"garbage collections: {collected[0]}, {collected[1]:.4f} s; "
                     f"checked questions: {sorted(self.kept)}")
        notes += [f"failed question {r['q']}: {r.get('error') or r}" for r in failed[:3]]
        return {
            "attempted": n,
            "failed": len(failed),
            "window_s": span,
            "metrics": {"cold_question_s": span / n},
            "notes": notes,
        }

    # ---- the check ----------------------------------------------------------
    def checked_questions(self) -> List[Dict]:
        return [r for r in self.records if r["q"] in self.kept and "key" in r]

    def compare(self, rec: Dict, control=None) -> Dict[str, Dict[str, float]]:
        """Numbers of one question's persisted artifact and answer; with
        ``control=(xp, dtype)``, also of the reference computed in that
        precision in the program's place, at the same columns."""
        chk = self.traffic["check"]
        art_dir = os.path.join(self.store_root, rec["key"])
        must = list(oracle.top_columns(self.cfg, art_dir, self.budget, chk["top"]))
        if rec["best_index"] >= 0:
            must.append(rec["best_index"])
        cols = oracle.sample_columns(len(self.space["n_sm"]), self.seed, rec["q"],
                                     chk["random"], must)
        asked = {k: v[cols] for k, v in oracle.permuted(self.space, self.seed, rec["q"]).items()}
        got_time, got_idx, got_hw = oracle.readback_columns(art_dir, cols)
        pos = int(np.searchsorted(cols, rec["best_index"])) if rec["best_index"] >= 0 else None
        answers = {"program": {"hw": got_hw, "time": got_time, "idx": got_idx, "pos": pos,
                               "gflops": rec["best_gflops"], "point": rec["best_point"]}}
        if control is not None:
            answers["control"] = oracle.control_answer(self.cfg, asked, *control, self.budget)
        return oracle.compare(self.cfg, asked, self.budget, answers)

    def check(self) -> Dict[str, Dict[str, float]]:
        """The numbers that decide ``correct``, each the worst over the
        checked questions, beside its limit."""
        limits = self.cfg["limits"]
        worst = {name: 0.0 if self.records else float("inf") for name in limits}
        for rec in self.checked_questions():
            try:
                got = self.compare(rec)["program"]
            except (OSError, KeyError, ValueError):  # unreadable artifact
                self.say(f"question {rec['q']}: artifact unreadable: "
                         f"{traceback.format_exc(limit=2)}")
                got = {name: float("inf") for name in limits}
            for name in limits:
                worst[name] = max(worst[name], got[name])
        return {name: {"value": worst[name], "limit": limits[name]} for name in limits}

    def close(self) -> None:
        if self._restore is not None:
            self._restore()
            self._restore = None
        if self.store_root is not None:
            shutil.rmtree(self.store_root, ignore_errors=True)
            self.store_root = None
