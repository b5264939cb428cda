"""Tier-1 collects the benchmark's seam test (`benchmark/test_harness_seam.py`:
a second, no server): the cells accepted before PR 30 still send the requests
and compare with the tables they did, a selector's promql, folds and tables,
and the `loader` / `reference` keys (PERF.md section 7, item 2, PR 30)."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmark.test_harness_seam import *  # noqa: E402,F401,F403
