#!/usr/bin/env python3
"""Find the knee of an open-loop serve cell: the highest offered rate
whose backlog does not grow over the window.

    python3 bench/sweep.py --workload qwen2-0.5b.serve-chat --rates 4,8,12 --seconds 30

Runs the cell once per rate in one process and prints one JSON line per
rate: the end-to-end metrics, how many requests were due and failed, and
the mean backlog over the window's first and last thirds.  The cell's
mix then fixes its rate at a share of the knee; the sweep is recorded in
``PERF.md``.  The benchmark's own runs never sweep.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def main(argv=None) -> int:
    from bench import harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[1])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    harness.cache_in_checkout(root)
    for rate in (float(r) for r in args.rates.split(",")):
        out = harness.run_cell(root, args.workload, args.seed, args.seconds,
                               False, t_start=time.perf_counter(),
                               mix_override={"rate_per_s": rate})
        rec = out["record"]
        print(json.dumps({
            "rate_per_s": rate, "metrics": out["metrics"],
            "attempted": out["attempted"], "failed": out["failed"],
            "backlog_first": rec["backlog_first"],
            "backlog_last": rec["backlog_last"],
            "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
