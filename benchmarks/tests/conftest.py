"""Tests of the benchmark harness: ``python -m pytest benchmarks/tests``.

They run on the CPU. The harness modules import as the benchmark's
command imports them, with ``benchmarks/`` and the checkout root on the
path.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)
