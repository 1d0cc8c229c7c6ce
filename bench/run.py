#!/usr/bin/env python3
"""Run one benchmark cell once; the last line of standard output is its
result as one JSON object.

    python3 bench/run.py --workload paper-gtx980.cold --seed 7 --seconds 30 --trace 0

Run from the root of a checkout on a host whose chips match the cell's
``chips`` in ``BENCHMARK.json``. ``--trace 1`` measures the same window
under the profiler and reports the per-layer metrics instead of the
end-to-end ones. Exits 2, printing no result, where JAX finds no TPU or
another number of chips, or where the program's sources are missing.
"""

import time

T_START = time.perf_counter()  # set-up is measured from here

import sys  # noqa: E402

from harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
