"""Run by hand: `JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q`.
Not under tests/, so tier-1's count and time do not move."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for p in (BENCH, os.path.dirname(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)
