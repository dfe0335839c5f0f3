"""The benchmark's own tests: `pytest benchmark/tests`, by hand, on the
CPU. They are no part of the repo's tier-1 suite under tests/."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"     # these tests never touch a chip

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for path in (BENCH, ROOT):
    if path not in sys.path:
        sys.path.insert(0, path)
