"""Benchmark harness — one entry per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full]

Outputs CSVs to bench_out/ and prints each table.  The LM roofline table
(beyond-paper) renders from artifacts/dryrun/ when present (produced by
launch/dryrun.py).

Without ``--only`` every lane runs in a process of its own, one after
another: a process that has touched JAX holds the chip, so a lane that
spawns worker processes (``online_federated``) could not start after
another lane in the same process.  Lanes share the persistent compile
cache (repro/compile_cache.py).
"""
from __future__ import annotations

import argparse
import subprocess
import sys
from pathlib import Path

LANES = ["table3", "table2", "table1", "online", "online_scale",
         "online_federated", "scenarios", "sched_scale", "hotpath",
         "roofline"]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="full-size sweeps (slow on 1 CPU core)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny configs (CI smoke lane; overrides --full)")
    ap.add_argument("--only", default=None, choices=LANES)
    ap.add_argument("--pallas", action="store_true",
                    help="serve the online benchmark on the Pallas hot path "
                         "(use_pallas=True; compiled on TPU, interpreter "
                         "mode elsewhere) -> bench_out/online_pallas.csv")
    ap.add_argument("--chaos", action="store_true",
                    help="fault-injected serving: checkpoint overhead, "
                         "shard-kill recovery + journal replay, degradation "
                         "ladder -> bench_out/online_chaos.csv (use with "
                         "--only online_scale)")
    args = ap.parse_args()
    quick = not args.full

    if args.only is None:
        for lane in LANES:
            subprocess.run([sys.executable, "-m", "benchmarks.run",
                            *sys.argv[1:], "--only", lane], check=True)
        return

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.only == "table3":
        from benchmarks import table3_ablation
        table3_ablation.run(quick=quick)
    if args.only == "table2":
        from benchmarks import table2_scaling
        table2_scaling.run(quick=quick)
    if args.only == "table1":
        from benchmarks import table1_accuracy
        table1_accuracy.run(quick=quick)
    if args.only == "online":
        from benchmarks import online_serving
        online_serving.run(quick=quick, smoke=args.smoke,
                           use_pallas=args.pallas)
    if args.only == "online_scale":
        from benchmarks import online_scale
        online_scale.run(quick=quick, smoke=args.smoke, chaos=args.chaos)
    if args.only == "online_federated":
        from benchmarks import online_federated
        online_federated.run(quick=quick, smoke=args.smoke)
    if args.only == "scenarios":
        from benchmarks import scenarios
        scenarios.run(quick=quick, smoke=args.smoke)
    if args.only == "sched_scale":
        from benchmarks import sched_scale
        sched_scale.run(quick=quick, smoke=args.smoke)
    if args.only == "hotpath":
        from benchmarks import hotpath
        hotpath.run(quick=quick, smoke=args.smoke)
    if args.only == "roofline":
        d = Path("artifacts/dryrun")
        if d.exists() and any(d.glob("*.json")):
            from repro.launch.roofline import load_records, render_table
            recs = load_records(d)
            print("\n== LM roofline (single-pod; see EXPERIMENTS.md) ==")
            print(render_table(recs, "16x16"))
        else:
            print("\n[roofline] no artifacts/dryrun JSONs; run "
                  "PYTHONPATH=src python -m repro.launch.dryrun first")


if __name__ == "__main__":
    main()
