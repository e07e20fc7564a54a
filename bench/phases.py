#!/usr/bin/env python3
"""Where a chunk boundary's time goes, from the engine's phase spans.

    python3 bench/phases.py --workload <name> --seed <n> --seconds 40 \
        --trace-seconds 10 [--dump <file.json>] [--rehearse]

Runs one cell's set-up and window exactly as `bench/run.py` does, with a
profiler trace of `--trace-seconds` in the middle of the window, and prints
one JSON line:

  chunks            chunks launched in the traced slice
  boundary_ms       the three boundary sums, ms per chunk (benchlib.spans)
  span_ms           each engine.* span's self time, ms per chunk
  idle_ms           device idle under each span (self coverage), ms per chunk
  step_chunk_ms     engine.step_chunk's time per chunk; the phases' share
  idle_share, busy_s, window_s, module_idle_s, idle_gaps
  ticks_per_s       session-ticks/s in each 5 s of the window, and the
                    means of the bins inside the traced slice and of those
                    before it (the profiler's cost)

A diagnostic beside the benchmark: nothing is compared with the reference,
and the line is no benchmark result. `--dump` writes the traced slice's
intervals in the form `bench/testdata/*.json` holds. Where the program
writes no engine span, the span keys read null and the rest stands.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def _bins(run, span_s: float) -> dict:
    """Session-ticks/s per PROFILE_S of the window; the traced slice is the
    middle `span_s` (harness.Run.window). The bins clear of the profiler
    are those before the slice, the one next to it aside: writing the trace
    out stalls the loop for seconds after it."""
    from benchlib import harness

    width = harness.PROFILE_S
    n = int(math.ceil(run.seconds / width))
    ticks = [0.0] * n
    for r in run.records.values():
        if r.returned is not None and run.t_window <= r.returned <= run.t_end:
            ticks[min(int((r.returned - run.t_window) / width), n - 1)] += r.arrival.ticks
    rates = [t / min(width, run.seconds - width * i) for i, t in enumerate(ticks)]
    t0 = max(0.0, (run.seconds - span_s) / 2)
    inside = [i for i in range(n) if t0 <= i * width and (i + 1) * width <= t0 + span_s]
    clear = [i for i in range(n) if (i + 1) * width < t0]

    def mean(idx):
        return sum(rates[i] for i in idx) / len(idx) if idx else None

    return {"bins": rates, "traced_bins": inside, "clear_bins": clear,
            "traced": mean(inside), "clear": mean(clear)}


def _dump(path: str, tr: dict, chunks: int, what: str) -> None:
    """The traced slice's intervals, events outside the window dropped."""
    from benchlib import trace

    lo, hi = trace.window(tr["spans"])

    def rows(events, keep):
        return [[x[0], int(x[1]), int(x[2]), *x[3:]] for x in events
                if keep(x)]

    ops = rows(tr["ops"], lambda x: x[2] > lo and x[1] < hi)
    mods = rows(tr["modules"], lambda x: x[2] > lo and x[1] < hi)
    spans = rows(tr["spans"], lambda x: x[2] >= lo and x[1] <= hi)
    with open(path, "w") as f:
        json.dump({"recorded": what, "chunks_in_trace": chunks,
                   "trace": {"ops": ops, "modules": mods, "spans": spans,
                             "devices": tr["devices"], "on_device": tr["on_device"]}},
                  f, separators=(",", ":"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="engine phase spans of one cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace-seconds", type=float, required=True)
    ap.add_argument("--dump", help="write the traced slice's intervals here")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at toy sizes; never a device result")
    args = ap.parse_args(argv)

    from benchlib import harness, registry, spans, system, trace

    cell = registry.Cell(args.workload)
    import jax

    devices = jax.local_devices()
    platform = devices[0].platform
    if args.rehearse != (platform == "cpu"):
        print(f"phases: JAX platform {platform!r}; --rehearse runs on the CPU "
              "only, and without it an accelerator is needed", file=sys.stderr)
        return 2
    if args.rehearse:
        harness.apply_rehearsal(cell)
    system.enable_persistent_cache()
    cell.check["trace_seconds"] = args.trace_seconds
    run = harness.Run(cell, args.seed, args.seconds, True, args.rehearse, T_PROCESS)
    run.setup()
    run.window()
    try:
        tr = spans.load(trace.find_xplane(run._trace_dir))
    finally:
        shutil.rmtree(run._trace_dir, ignore_errors=True)
    chunks = run.trace_launches
    red = spans.reduce(tr)
    if red is None:
        print("phases: no traced window with an operation in it", file=sys.stderr)
        return 1
    if args.dump:
        what = (f"{devices[0].device_kind}, one chip, bench/phases.py "
                f"{args.workload} (seed {args.seed}), a {args.trace_seconds:g} s "
                f"traced window, {chunks} chunks")
        _dump(args.dump, tr, chunks, what)
    per = {n: 1e3 * t / max(chunks, 1) for n, t in red["span_self_s"].items()}
    line = {
        "workload": args.workload, "platform": platform,
        "kind": devices[0].device_kind, "chunks": chunks,
        "boundary_ms": spans.boundary_ms(red, chunks),
        "span_ms": per or None,
        "idle_ms": {n: 1e3 * t / max(chunks, 1)
                    for n, t in red["idle_by_span_s"].items()},
        "step_chunk_ms": 1e3 * red["step_chunk_s"] / max(chunks, 1) if per else None,
        "idle_share": 100.0 * (1.0 - red["busy_s"] / red["window_s"]),
        "busy_s": red["busy_s"], "window_s": red["window_s"],
        "module_idle_s": red["module_idle_s"],
        "idle_gaps": red["idle_gaps"],
        "ticks_per_s": _bins(run, args.trace_seconds),
    }
    for note in spans.notes(red, chunks):
        print(f"phases: {note}", file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
