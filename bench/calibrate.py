#!/usr/bin/env python3
"""Readings for the limits of a cell's correctness numbers, and the
limits set from them.

    python3 bench/calibrate.py --workload <name> --seeds 1,2,3 --seconds 5 [--write]
    python3 bench/calibrate.py --workload <name> --from runs.out ... [--write]

For each seed, in one process, runs the cell with a short window and
prints one JSON line: the program's numbers, and the same numbers for
the control (the reference computed in float8, put in the program's
place) and, for training cells, for a planted fault (half of the batch
left out).  The benchmark's own runs never run the control.

Then, per number: the lower reading is the largest the program gave;
the upper is the least of the control's smallest reading, where that is
three times the lower or more, a fault's smallest reading, where that
is ten times the lower or more, and, for a training cell's ``grad``,
``grad_diff`` and ``change``, 1 (a step that leaves its state unchanged reads 1 by their
measure) where that is three times the lower or more.  The limit lies
two thirds of the way from the lower to the upper on a log scale (more
room above the lower, since fresh seeds read higher).  ``unserved`` is
exact: limit 0.  A training cell's number with no upper reading, whose
program readings all lie under a tenth, is listed as not compared; any
other number with no upper reading is an error, and nothing is written.
``--from`` runs nothing: it takes the rows from files of earlier output,
this tool's lines or the result lines of ``bench/run.py`` (every JSON
line with ``checks``; ``calibration`` where the run was made with
``--calibrate 1``; the seed from the line or else from the last number
in the file's name).
With ``--write`` the limits and their readings go to
``bench/limits/<workload>.json``.
"""

import argparse
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

EXACT = {"unserved": 0.0}
UNCHANGED_READS_1 = ("grad", "change", "grad_diff")
#: a training number with no upper reading may go uncompared only if
#: every sound run reads under this
UNCOMPARED_BELOW = 0.1


class NoUpperReading(RuntimeError):
    """A number that no control or fault separates from sound runs."""


def limits_from(rows: list, training: bool) -> dict:
    out = {}
    names = sorted({n for r in rows for n in r["checks"]})
    for n in names:
        lower = max(r["checks"][n]["value"] for r in rows)
        entry = {"lower": lower, "seeds": [r["seed"] for r in rows]}
        if n in EXACT:
            entry.update(limit=EXACT[n], why="exact")
            out[n] = entry
            continue
        alts = {}
        for r in rows:
            for alt, nums in (r.get("calibration") or {}).items():
                alts.setdefault(alt, []).append(nums[n])
        entry["alternatives"] = {a: min(v) for a, v in alts.items()}
        ups = [v for a, v in entry["alternatives"].items()
               if v >= (3 if a == "control" else 10) * lower]
        if training and n in UNCHANGED_READS_1 and 1.0 >= 3 * lower:
            ups.append(1.0)
        if not ups:
            if not training or lower >= UNCOMPARED_BELOW:
                raise NoUpperReading(f"{n}: lower {lower!r}, control and "
                                     f"faults {entry['alternatives']}")
            entry.update(limit=None, why="no upper reading: not compared")
            out[n] = entry
            continue
        upper = min(ups)
        entry.update(upper=upper,
                     limit=lower ** (1 / 3) * upper ** (2 / 3))
        out[n] = entry
    return out


def rows_from(files) -> list:
    """The rows in files of earlier output (see the module's text)."""
    rows = []
    for path in files:
        with open(path) as fh:
            for line in fh:
                line = line.strip()
                if not line.startswith("{"):
                    continue
                try:
                    d = json.loads(line)
                except ValueError:
                    continue
                if "checks" in d:
                    seed = d.get("seed")
                    if seed is None:
                        m = re.search(r"(\d+)\D*$", os.path.basename(path))
                        seed = int(m.group(1)) if m else None
                    rows.append({"seed": seed, "checks": d["checks"],
                                 "calibration": d.get("calibration") or {}})
    return rows


def main(argv=None) -> int:
    from bench import harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[1])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds")
    ap.add_argument("--from", dest="files", nargs="+", default=())
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--write", action="store_true")
    args = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    harness.cache_in_checkout(root)
    rows = rows_from(args.files)
    for seed in (int(s) for s in (args.seeds or "").split(",") if s):
        out = harness.run_cell(root, args.workload, seed, args.seconds,
                               False, t_start=time.perf_counter(),
                               calibrate=True)
        row = {"seed": seed, "checks": out["checks"],
               "calibration": out["calibration"],
               "metrics": out["metrics"], "record": out["record"],
               "device": out["device"]}
        rows.append(row)
        print(json.dumps(row), flush=True)
    mix = harness.load_json(os.path.join(root, "bench", "mixes", next(
        w["traffic"] for w in harness.load_json(os.path.join(
            root, "BENCHMARK.json"))["workloads"]
        if w["name"] == args.workload) + ".json"))
    try:
        found = limits_from(rows, mix["kind"].startswith("train"))
    except NoUpperReading as e:
        print(f"calibrate: no limit holds for {e}", file=sys.stderr)
        return 1
    print(json.dumps({"limits_from_readings": found}), flush=True)
    if args.write:
        limits = {n: e["limit"] for n, e in found.items()
                  if e["limit"] is not None}
        skip = sorted(n for n, e in found.items() if e["limit"] is None)
        os.makedirs(os.path.join(root, "bench", "limits"), exist_ok=True)
        path = os.path.join(root, "bench", "limits", args.workload + ".json")
        with open(path, "w") as fh:
            json.dump({"limits": limits, "not_compared": skip,
                       "readings": found}, fh, indent=1)
            fh.write("\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
