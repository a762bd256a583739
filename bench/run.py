#!/usr/bin/env python3
"""One run of one benchmark cell.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine that holds the chips the
cell asks for.  The last line of standard output is the result object;
the last lines of standard error give each number compared for
``correct`` beside its limit.  Without a TPU, or with fewer chips than
the cell asks for, it prints no result and exits non-zero.
"""

import argparse
import os
import sys
import time

T_START = time.perf_counter()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[1])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--calibrate", type=int, choices=(0, 1), default=0,
                    help="also read the control and the planted faults "
                    "after the window (bench/calibrate.py --from)")
    return ap.parse_args(argv)


if __name__ == "__main__":
    from bench import harness
    sys.exit(harness.main(parse(), T_START))
