"""A closed loop of cold LM codesign questions, one at a time.

Each question is what a capacity planner's ``cli query --workload lm``
does on a store that does not hold the key: construct an ``LMServer`` over
the configuration's model and shapes (built once, at set-up, through the
program's own ``lm_workload``) and the whole mesh space, with
``engine="auto"`` and no batching window, and ask it for the best mesh
within the configuration's chip budget under the workload's uniform mix.
That runs the server's build path, one jitted grid per cell on the device,
the store's staged write and reload, and the query's reduction.

Every question gets the whole mesh space in a new order drawn from the
seed: identical work at identical shapes (nothing recompiles), but a new
content address, so no store or in-process memo can answer it. A question
counts as failed when it raises, answers no design (or one at no finite,
positive GFLOP/s, which is how a workload that no mesh holds whole is
answered), reuses an address, or was not built by its server. A program
that cannot answer the question at all fails its warm-up question, so the
run stops during set-up with a non-zero exit.

The benchmark's own host spans mark each question, the server's
construction, the query, and inside it the program's ``lm_codesign()``
and ``ArtifactStore.put``. They take the names the stencil cell gives its
driver call and its store write (``bench.codesign``, ``bench.store_put``),
so the same readers measure the same layers in both cells; they are set
here by wrapping those two calls, not inside the program.

Which questions are checked, and when artifacts are removed, is as in
``cold_question.py``. After the window the checked artifacts are read back
from the store and compared with ``lm_oracle.py`` at every mesh.
"""

from __future__ import annotations

import gc
import math
import os
import shutil
import tempfile
import time
import traceback
from typing import Callable, Dict, List, Optional

import numpy as np

import lm_oracle
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
        """Build the workload, the store and the span wrappers, and answer
        one warm-up question (it compiles or loads the cell's programs)."""
        import jax
        from repro.configs.base import ShapeSpec
        from repro.core.lmcells import LMHardwareSpace, lm_workload
        from repro.service import server as server_mod
        from repro.service.query import QueryRequest
        from repro.service.store import ArtifactStore

        cfg = self.cfg
        self.annotate = jax.profiler.TraceAnnotation
        self.LMHardwareSpace = LMHardwareSpace
        self.LMServer = server_mod.LMServer
        self.space = lm_oracle.hardware_space(cfg)
        shapes = {c["op"]: ShapeSpec(c["shape"], c["seq_len"], c["global_batch"],
                                     "decode" if c["op"] == "moe_dispatch" else c["op"])
                  for c in cfg["cells"]}
        self.workload = lm_workload(archs=[cfg["arch"]], name=cfg["name"], shapes=shapes)
        got = [(c.op, c.shape.seq_len, c.shape.global_batch) for c in self.workload.cells]
        want = [(c["op"], c["seq_len"], c["global_batch"]) for c in cfg["cells"]]
        if got != want:
            raise RuntimeError(f"the program builds cells {got}, the configuration states {want}")
        self.request = QueryRequest(max_area=self.budget)

        self.store_root = tempfile.mkdtemp(prefix="bench-store-")
        self.store = ArtifactStore(self.store_root)
        real_codesign, real_put = server_mod.lm_codesign, self.store.put

        def timed(name, call):
            def wrapped(*args, **kwargs):
                t0 = time.perf_counter()
                try:
                    with self.annotate(f"bench.{name}"):
                        return call(*args, **kwargs)
                finally:
                    self._parts[name] = self._parts.get(name, 0.0) + time.perf_counter() - t0
            return wrapped

        server_mod.lm_codesign = timed("codesign", real_codesign)
        self.store.put = timed("store_put", real_put)
        self._restore = lambda: setattr(server_mod, "lm_codesign", real_codesign)

        warm = self.question(0)
        if not warm["ok"]:
            raise RuntimeError(f"warm-up question failed: {warm.get('error', warm)}")
        self.say(f"warm-up question: {warm['s']:.3f} s, best {warm['best_point']}")
        self.store.delete(warm["key"])

    def question(self, q: int) -> Dict:
        hw = self.LMHardwareSpace(**lm_oracle.permuted(self.space, self.seed, q))
        rec: Dict = {"q": q, "ok": False}
        self._parts = {}
        t0 = time.perf_counter()
        try:
            with self.annotate("bench.question"):
                with self.annotate("bench.server"):
                    srv = self.LMServer(self.store, workload=self.workload, hw=hw,
                                        engine="auto", batch_window=0.0)
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
        # a design at no finite, positive GFLOP/s is no answer: no mesh
        # holds every cell of the workload
        answered = rec["best_index"] >= 0 and 0.0 < rec["best_gflops"] < math.inf
        rec["ok"] = fresh and rec["built"] and answered
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
            f"lm-cold: {n} questions, {sum(r.get('fresh', False) for r in self.records)} new "
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
            notes.append(f"answered: {timed[-1]['best_point']} at {timed[-1]['best_gflops']:.6g} "
                         f"model GFLOP/s")
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
        precision in the program's place."""
        asked = lm_oracle.permuted(self.space, self.seed, rec["q"])
        got_time, got_idx, got_hw = lm_oracle.readback(os.path.join(self.store_root, rec["key"]))
        pos = rec["best_index"] if rec["best_index"] >= 0 else None
        answers = {"program": {"hw": got_hw, "time": got_time, "idx": got_idx, "pos": pos,
                               "gflops": rec["best_gflops"], "point": rec["best_point"]}}
        if control is not None:
            answers["control"] = lm_oracle.control_answer(self.cfg, asked, *control, self.budget)
        return lm_oracle.compare(self.cfg, asked, self.budget, answers)

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
