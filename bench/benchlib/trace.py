"""From a profiler trace to the numbers the per-layer metrics read.

`load` turns the profiler's `.xplane.pb` into plain lists of intervals, in
nanoseconds on the trace's one clock:

  ops      every operation that ran on a device ("XLA Ops" lines of the
           `/device:...` planes), as (name, start, end, device)
  modules  every program execution on a device ("XLA Modules" lines)
  spans    the harness's own host spans (`jax.profiler.TraceAnnotation`):
           step_chunk, submit and the traced window itself

Off an accelerator (the CPU rehearsal) there is no device plane; the
operations XLA's CPU client runs (host events that carry an `hlo_op`
stat) stand in for the device's, and there are no modules.

`reduce` computes, over the traced window: the union of each device's
operation intervals (busy), its complement (idle gaps, each labelled by the
host span that covers most of it), the idle time between program
executions, and the operations that took the most time (self time: a loop
op does not count the ops of its body). `reduce` works on
the plain lists, so a recorded trace can be checked without the profiler.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

HOST_SPANS = ("submit", "step_chunk", "trace_window")
WINDOW_SPAN = "trace_window"

Interval = Tuple[float, float]


def find_xplane(directory: str) -> str:
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return paths[-1]


def short(name: str) -> str:
    """An HLO op's name without its signature, a custom call with its target."""
    head = name.split(" = ")[0].lstrip("%")
    if "custom_call_target=" in name:
        head += " [" + name.split('custom_call_target="')[-1].split('"')[0] + "]"
    return head


def load(path: str) -> dict:
    from jax.profiler import ProfileData

    prof = ProfileData.from_file(path)
    ops, modules, spans, cpu_ops = [], [], [], []
    devices = []
    for plane in prof.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    found = [(short(e.name), e.start_ns, e.end_ns, plane.name)
                             for e in line.events]
                    if found and plane.name not in devices:
                        devices.append(plane.name)
                    ops.extend(found)
                elif line.name == "XLA Modules":
                    modules.extend((e.name.split("(")[0], e.start_ns, e.end_ns,
                                    plane.name) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS:
                        spans.append((e.name, e.start_ns, e.end_ns))
                    elif e.duration_ns > 0 and any(
                        k == "hlo_op" for k, _ in e.stats
                    ):
                        cpu_ops.append((e.name, e.start_ns, e.end_ns, "/host:CPU"))
    if not devices:
        ops, devices = cpu_ops, ["/host:CPU"]
    modules = [m for m in modules if m[3] in devices]
    return {"ops": ops, "modules": modules, "spans": spans,
            "devices": sorted(devices), "on_device": devices != ["/host:CPU"]}


def self_times(ops, lo: float, hi: float) -> Dict[str, float]:
    """Each op's time in [lo, hi] less that of the ops nested inside it (a
    loop's body inside the loop), summed by name."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[list] = []  # [name, start, end, self]
    device = None
    for name, s, e, dev in sorted(ops, key=lambda o: (o[3], o[1], -o[2])):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        while stack and (stack[-1][2] <= s or dev != device):
            top = stack.pop()
            out[top[0]] += top[3]
        if stack and e <= stack[-1][2]:
            stack[-1][3] -= e - s
        stack.append([name, s, e, e - s])
        device = dev
    for top in stack:
        out[top[0]] += top[3]
    return out


def union(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    """Merge intervals, clipped to [lo, hi]."""
    clipped = sorted((max(s, lo), min(e, hi)) for s, e in intervals
                     if e > lo and s < hi)
    merged: List[List[float]] = []
    for s, e in clipped:
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The complement of merged `busy` within [lo, hi]."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def label(gap: Interval, spans: Sequence[Tuple[str, float, float]]) -> str:
    """The host span that covers most of the gap; 'harness' where none does."""
    best, covered = "harness", 0.0
    for name, s, e in spans:
        if name == WINDOW_SPAN:
            continue
        c = min(e, gap[1]) - max(s, gap[0])
        if c > covered:
            best, covered = name, c
    return best


def window(spans: Sequence[Tuple[str, float, float]]) -> Optional[Interval]:
    for name, s, e in spans:
        if name == WINDOW_SPAN:
            return (s, e)
    return None


def reduce(tr: dict, top: int = 10) -> Optional[dict]:
    """Busy, idle and breakdown over the traced window; None with no window
    or no operation in it.

    Returns seconds: busy_s (mean over devices of the union of operation
    intervals), window_s, module_idle_s (mean over devices of the time no
    program ran), and `device_ops` / `idle_gaps` lists of [name, seconds],
    longest first, `top` entries each.
    """
    win = window(tr["spans"])
    if win is None:
        return None
    lo, hi = win
    by_dev: Dict[str, List[Interval]] = defaultdict(list)
    for name, s, e, dev in tr["ops"]:
        if e > lo and s < hi:
            by_dev[dev].append((s, e))
    if not by_dev:
        return None
    op_time = self_times(tr["ops"], lo, hi)
    devices = tr["devices"]
    busy_ns, gap_list = 0.0, []
    for dev in devices:
        merged = union(by_dev.get(dev, []), lo, hi)
        busy_ns += sum(e - s for s, e in merged)
        gap_list.extend(gaps(merged, lo, hi))
    module_idle_ns = None
    if tr["modules"]:
        module_idle_ns = 0.0
        mods = defaultdict(list)
        for _, s, e, dev in tr["modules"]:
            mods[dev].append((s, e))
        for dev in devices:
            merged = union(mods.get(dev, []), lo, hi)
            module_idle_ns += (hi - lo) - sum(e - s for s, e in merged)
        module_idle_ns /= len(devices)
    gap_list.sort(key=lambda g: g[1] - g[0], reverse=True)
    ops_sorted = sorted(op_time.items(), key=lambda kv: kv[1], reverse=True)
    ops_sorted = [kv for kv in ops_sorted if kv[1] > 0]
    return {
        "busy_s": busy_ns / len(devices) * 1e-9,
        "window_s": (hi - lo) * 1e-9,
        "module_idle_s": None if module_idle_ns is None else module_idle_ns * 1e-9,
        "device_ops": [[n, t * 1e-9] for n, t in ops_sorted[:top]],
        "idle_gaps": [[label(g, tr["spans"]), (g[1] - g[0]) * 1e-9]
                      for g in gap_list[:top]],
    }
