"""Tests of the benchmark itself, run on a CPU: ``python -m pytest chipbench/tests``.

The harness's own modules and the program's package go on the path here;
every test that runs the program runs it at a tiny size."""

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
