"""Tests of the benchmark itself (run by hand: ``python -m pytest bench/tests``).

They run on the CPU at small sizes: the harness's look for a TPU is
skipped, everything else of a run is driven as on the chip."""

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for path in (BENCH, os.path.join(ROOT, "src")):
    if path not in sys.path:
        sys.path.insert(0, path)
