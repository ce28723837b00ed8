#!/usr/bin/env python3
"""Chip benchmark entry point.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine that holds the chips the cell
asks for.  The last line of standard output is the result object; the
numbers the correctness comparison read, each beside its limit, are the
last lines of standard error.  Without a TPU, or with fewer chips than the
cell needs, it exits with code 2 and prints no result.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
