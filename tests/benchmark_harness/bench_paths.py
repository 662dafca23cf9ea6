"""Where the benchmark is, for its tests: importing this puts the repo's
root and benchmark/ on sys.path, the way `python3 benchmark/run.py` has
them."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "benchmark")
for _p in (ROOT, BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)
