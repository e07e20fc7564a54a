#!/usr/bin/env python3
"""The benchmark's one command: one run of one cell.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs on the machine it is started on, on its accelerator, and prints as the
last line of standard output one JSON object: correct, attempted, failed,
metrics (the cell's end-to-end metrics, or with --trace 1 its per-layer
metrics), device, with --trace 1 a breakdown, and last the numbers compared
with the plain reference beside their limits (also the last lines of
standard error). Exits 2 without printing a result where JAX finds no
accelerator or fewer chips than the cell asks for.

    python3 bench/run.py --workload <name> --seed 1 --seconds 1 --trace 0 --rehearse

is the CPU rehearsal: the same run at toy sizes (bench/rehearsal.json),
Pallas interpreted. Its line says "rehearsal": true, carries its numbers
under "rehearsal_metrics" only, and always has "correct": false.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="one run of one benchmark cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at toy sizes; never a device result")
    args = ap.parse_args(argv)
    if args.seed < 0:
        print("bench: --seed must be a non-negative whole number", file=sys.stderr)
        return 2

    from benchlib import registry

    try:
        cell = registry.Cell(args.workload)
    except (KeyError, FileNotFoundError) as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 2
    try:
        from benchlib import system
    except ImportError as exc:
        print(f"bench: the system under test (src/repro) cannot be imported: {exc}",
              file=sys.stderr)
        return 2
    from benchlib import harness

    try:
        harness.validate(cell)
    except ValueError as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 2

    import jax

    devices = jax.local_devices()
    platform = devices[0].platform
    if args.rehearse:
        if platform != "cpu":
            print("bench: --rehearse runs on the CPU only (JAX_PLATFORMS=cpu)",
                  file=sys.stderr)
            return 2
        harness.apply_rehearsal(cell)
    elif platform not in ("tpu", "gpu"):
        print(f"bench: no accelerator (JAX platform {platform!r}); this "
              "benchmark measures a chip (--rehearse is the CPU rehearsal)",
              file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        hint = (" (XLA_FLAGS=--xla_force_host_platform_device_count="
                f"{cell.chips} gives the rehearsal as many)" if args.rehearse else "")
        print(f"bench: {args.workload} needs {cell.chips} chips, JAX finds "
              f"{len(devices)}{hint}", file=sys.stderr)
        return 2
    system.enable_persistent_cache()
    harness.CompileCounter.get()
    res = harness.execute(cell, args.seed, args.seconds, bool(args.trace),
                          args.rehearse, T_PROCESS)
    for note in res.notes:
        print(f"bench: {note}", file=sys.stderr)
    line = res.line
    if args.rehearse:
        checks = line.pop("checks")
        line = {"correct": False, "rehearsal": True,
                "outputs_match": line["correct"],
                "attempted": line["attempted"], "failed": line["failed"],
                "metrics": {}, "device": line["device"],
                "rehearsal_metrics": line["metrics"], "checks": checks}
    for name, value, limit in res.checks:
        print(f"check {name}: {value!r} (limit {limit!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
