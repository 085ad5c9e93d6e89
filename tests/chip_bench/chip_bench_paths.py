"""Where the harness lives, for the tests of this directory."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks", "chip")
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)
