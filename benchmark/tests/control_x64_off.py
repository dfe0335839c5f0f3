"""The control, on the chip at a cell's own size: the program with 64-bit
arithmetic switched off, in run.py's place.

    python3 benchmark/tests/control_x64_off.py --workload <cell> --seed <n> --seconds <s> --trace 0

The configurations guarantee exact decimals, keys and counts, which need
64-bit lanes; the engine sets `jax_enable_x64` when it is imported, and
32-bit lanes are the step down that would tempt a later PR (they are what
the TPU is fast at). The control has failed — as it must — when this
exits non-zero without a result line, or prints `correct: false`.
test_rehearsal.py holds the same control at `tiny` on the CPU.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

import jax          # noqa: E402
import trino_tpu    # noqa: E402,F401  (sets 64-bit mode on import)

jax.config.update("jax_enable_x64", False)

import run          # noqa: E402

if __name__ == "__main__":
    sys.exit(run.main())
