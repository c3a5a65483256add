#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload online64.steady --seed 7 --seconds 30 \
        --trace 0

Set-up (telemetry from the seed, the server, warm-up) is timed as
`setup_s`; then the closed loop runs for `--seconds`.  With `--trace 0` the
result carries the cell's end-to-end metrics, with `--trace 1` its per-layer
metrics, read from a profiler trace of the window's first seconds and from
the program's spans.  Either way the calls the window drove are checked
against the plain reference afterwards.  Each number compared is printed
beside its limit as the last lines on standard error and under `compared`,
the last key of the result, which is the last line on standard output.

Exits nonzero with no result where JAX finds no TPU, fewer chips than the
cell asks for, or a device that `bench/peaks.json` does not list.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    from bench.harness import Cell, NoChip, run_cell

    cell = Cell.load(args.workload)
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          t_start=T_START, log=log)
    except NoChip as e:
        log(str(e))
        return 2
    compared = result.pop("compared")
    for name, c in compared.items():
        log(f"compared {name} {c['value']!r} limit {c['limit']!r}")
    result["compared"] = compared
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
