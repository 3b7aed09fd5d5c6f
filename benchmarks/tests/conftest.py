"""The benchmark's own tests run on the CPU backend, one device: set before
JAX is imported. (`python -m pytest benchmarks/tests -q`)"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.dirname(BENCH_DIR), BENCH_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)
