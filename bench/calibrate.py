#!/usr/bin/env python3
"""Read the two readings each correctness limit is set from.

    python bench/calibrate.py --workload online64.steady --seconds 4 \
        --seeds 3000000001 3000000002 ...

Runs the cell once per seed, in one process, with a short window at the
cell's own size, and compares the calls the window drove three times: as
the program made them; with the control in the program's place (the plain
reference at 'high', three bfloat16 passes, one step below the float32 at
'highest' that the configurations state); and with a refit step that leaves
out half of each slot's windows.  Prints one JSON line per seed and, last,
for each number the largest program reading and the smallest control and
fault readings.  A limit lies between them; see PERF.md.
The benchmark's own runs do not run this.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--detail", help="append each call's numbers to this "
                    "file, one JSON line per seed")
    args = ap.parse_args()

    from bench import reference
    from bench.harness import Cell, run_cell

    check = reference.check
    sides = (("program", None), ("control", "high"), ("half", "half"))
    seen = {}

    def every_side(cell, traffic, srv, rec, *, stand_in=None):
        seen["detail"] = {side: [] for side, _ in sides}
        for side, mode in sides:
            seen[side] = check(cell, traffic, srv, rec, stand_in=mode,
                               detail=seen["detail"][side])
        return seen["program"]
    reference.check = every_side

    rows = []
    for seed in args.seeds:
        t0 = time.perf_counter()
        run_cell(Cell.load(args.workload), seed, args.seconds, False,
                 log=lambda s: print(s, file=sys.stderr, flush=True))
        row = {"seed": seed, "seconds": time.perf_counter() - t0,
               **{side: {k: c["value"] for k, c in seen[side].items()}
                  for side, _ in sides}}
        rows.append(row)
        print(json.dumps(row), flush=True)
        if args.detail:
            with open(args.detail, "a") as f:
                f.write(json.dumps({"seed": seed, **seen["detail"]}) + "\n")
    names = rows[0]["program"]
    print(json.dumps({
        "workload": args.workload, "seeds": len(rows),
        "program_max": {k: max(r["program"][k] for r in rows) for k in names},
        "control_min": {k: min(r["control"][k] for r in rows)
                        for k in names},
        "half_min": {k: min(r["half"][k] for r in rows)
                     for k in names}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
