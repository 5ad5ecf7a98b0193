#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 benchmark/run.py --workload linsys.posv --seed 7 --seconds 10 \
        --trace 0

The last line of standard output is the result object; the numbers that
decided ``correct`` end standard error.  A host whose JAX finds no TPU,
or fewer chips than the cell asks for, exits 2 and prints no result.
"""

import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmark import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_start=T_START))
