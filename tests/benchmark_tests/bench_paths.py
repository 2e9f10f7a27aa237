"""Shared by the benchmark's tests: where things are, and imports of the
benchmark's own library (`benchmark/kbench`), which is not a package of the
program."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
