"""Harness self-tests: CPU, seconds.  Run from the checkout's root:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""

import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)
