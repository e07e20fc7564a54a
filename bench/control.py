#!/usr/bin/env python3
"""Readings that a cell's limits are set from (PERF.md gives them).

    python3 bench/control.py --workload <name> --seeds 11 12 13 --seconds 10 \
        [--precision bf16_coupling] [--rehearse]

Runs the cell once per seed, in one process, as bench/run.py would (the
cell's own sizes and load, a window of --seconds), and prints per seed one
JSON line with the numbers the check compares. Without --precision they
are the lower readings, from sound runs; with --precision bf16_coupling,
one bfloat16 pass of the coupling product, the program's own path below
the configuration's precision, they are the control's, the upper
readings. The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="limit readings for a cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--precision", default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    from benchlib import harness, registry, system

    import jax

    if not args.rehearse and jax.devices()[0].platform == "cpu":
        print("control: no accelerator (use --rehearse on the CPU)", file=sys.stderr)
        return 2
    system.enable_persistent_cache()
    harness.CompileCounter.get()
    for seed in args.seeds:
        cell = registry.Cell(args.workload)
        if args.rehearse:
            harness.apply_rehearsal(cell)
        cell.config["plan"]["precision"] = args.precision
        res = harness.execute(cell, seed, args.seconds, False, args.rehearse,
                              time.perf_counter())
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "precision": args.precision, "sessions": len(res.sample),
            "program": {**res.gaps, "failed": res.line["failed"]},
            "correct": res.line["correct"],
            "metrics": {k: v["value"] for k, v in res.line["metrics"].items()},
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
