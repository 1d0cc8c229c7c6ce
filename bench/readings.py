#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from (not part of a run).

    python3 bench/readings.py --workload paper-gtx980.cold --seeds 11 12 13 \\
        --control-seeds 3

For each seed, in one process: the cell's cold questions at its own size
(as many as a run checks), each persisted artifact and answer compared
with the reference (``cell_err``, ``best_err``), as a run compares them;
for the first ``--control-seeds`` seeds also the control: the same
reference computed in bfloat16, the precision below the configuration's
float32, put in the program's place at the same columns. One JSON line
per seed, then a summary line with the largest sound reading and the
smallest control reading of each number.
"""

import argparse
import json
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args(argv)

    import harness

    found = harness.load_cell(args.workload)
    _, devices = harness.start_jax(found["cell"]["chips"])
    import jax.numpy as jnp

    loop_mod = harness.load_module(f"{harness.BENCH}/loops/{found['traffic']['loop']}.py", "loop")
    say = lambda s: print(s, file=sys.stderr, flush=True)
    loop = loop_mod.Loop(found["config"], found["traffic"], args.seeds[0], say)
    sound, control = {}, {}
    try:
        loop.setup()
        for n, seed in enumerate(args.seeds):
            loop.seed, loop.records = seed, []
            win = loop.window(0.0)
            t0 = time.perf_counter()
            line = {"seed": seed, "questions": win["attempted"], "failed": win["failed"],
                    "cold_question_s": win["metrics"]["cold_question_s"]}
            for rec in loop.checked_questions():
                got = loop.compare(rec, control=(jnp, jnp.bfloat16) if n < args.control_seeds else None)
                for who, out in (("program", sound), ("control", control)):
                    for name, v in got.get(who, {}).items():
                        line[f"{who}.{name}"] = max(line.get(f"{who}.{name}", 0.0), v)
            for key, v in line.items():
                who, _, name = key.partition(".")
                if who == "program":
                    sound[name] = max(sound.get(name, 0.0), v)
                elif who == "control":
                    control[name] = min(control.get(name, float("inf")), v)
            line["check_s"] = time.perf_counter() - t0
            for rec in loop.records:
                if "key" in rec:
                    loop.store.delete(rec["key"])
            print(json.dumps(line), flush=True)
    finally:
        loop.close()
    print(json.dumps({"workload": args.workload, "seeds": len(args.seeds),
                      "lower": sound, "control_min": control,
                      "device": {"kind": devices[0].device_kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
