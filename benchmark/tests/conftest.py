"""Tests of the benchmark's own yardstick. They run on the CPU and never
need a chip: `python -m pytest benchmark/tests -q` from the repo's root."""

import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH_DIR)
for p in (REPO, BENCH_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)
