"""The engine's phase spans in a profiler trace: self times, and each device
idle gap put down to the phase the host was in.

The engine (`src/repro/serve/reservoir.py`) writes one host span per phase
of a chunk boundary, on the profiler's clock:

  engine.step_chunk  the whole call (label only)
  engine.retire      the finals gather, the freeing scatter, quarantines
  engine.admit       scheduler admissions, readout padding, the admit scatters
  engine.assemble    the u / mask block over the running lanes
  engine.launch      mask and u to the device, tick_chunk and the readouts
  engine.harvest     per-session slicing and copies
  engine.fetch       the host blocked on the device, plus the copy (label only)
  engine.nan_guard   the isfinite scan
  engine.finalize    result recording

`load` is `benchlib.trace.load` with these spans kept beside the harness's;
`reduce` is `benchlib.trace.reduce` with more keys and the idle gaps
labelled by self coverage (`label`). Both work on the plain lists, so a
recorded trace can be checked without the profiler.
"""

from __future__ import annotations

from collections import defaultdict
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from benchlib import trace

PREFIX = "engine."
STEP = "engine.step_chunk"
UNCOVERED = "harness"  # idle under no span at all

# The three boundary sums, each the summed self time of its spans per chunk.
# engine.fetch has none: it grows when the host gets faster.
BOUNDARY = {
    "boundary_assemble_ms.closed": ("engine.retire", "engine.admit",
                                    "engine.assemble"),
    "boundary_launch_ms.closed": ("engine.launch",),
    "boundary_harvest_ms.closed": ("engine.harvest", "engine.nan_guard",
                                   "engine.finalize"),
}

Span = Tuple[str, float, float]
Interval = Tuple[float, float]


def load(path: str) -> dict:
    """`trace.load`, with every host span whose name starts with `engine.`
    added to `spans`."""
    from jax.profiler import ProfileData

    tr = trace.load(path)
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                tr["spans"].extend((e.name, e.start_ns, e.end_ns)
                                   for e in line.events
                                   if e.name.startswith(PREFIX))
    return tr


def self_seconds(spans: Sequence[Span], lo: float, hi: float) -> Dict[str, float]:
    """Each program span's time in [lo, hi] less that of the program spans
    nested inside it, summed by name, in seconds: the rule `trace.self_times`
    applies to ops."""
    program = [(n, s, e, "host") for n, s, e in spans if n.startswith(PREFIX)]
    return {n: t * 1e-9 for n, t in trace.self_times(program, lo, hi).items()}


def _nested(i: int, j: int, spans: Sequence[Span]) -> bool:
    """Span j nests in span i: i starts no later and ends no earlier (of two
    with the same bounds, the later listed nests in the earlier)."""
    _, si, ei = spans[i]
    _, sj, ej = spans[j]
    return i != j and si <= sj and ej <= ei and (si, -ei, i) < (sj, -ej, j)


def self_cover(gap: Interval, spans: Sequence[Span]) -> List[Tuple[str, float]]:
    """(name, self coverage) of each span that overlaps the gap: the part of
    the gap it covers that no span nested inside it covers. The traced
    window itself is no span here."""
    g0, g1 = gap
    over = [sp for sp in spans
            if sp[0] != trace.WINDOW_SPAN and sp[1] < g1 and sp[2] > g0]
    out = []
    for i, (name, s, e) in enumerate(over):
        a0, a1 = max(s, g0), min(e, g1)
        inner = [(over[j][1], over[j][2]) for j in range(len(over))
                 if _nested(i, j, over)]
        out.append((name, (a1 - a0) - sum(y - x for x, y in trace.union(inner, a0, a1))))
    return out


def _most(cover: List[Tuple[str, float]]) -> str:
    best, covered = UNCOVERED, 0.0
    for name, c in cover:
        if c > covered:
            best, covered = name, c
    return best


def label(gap: Interval, spans: Sequence[Span]) -> str:
    """The span with the most self coverage of the gap; 'harness' where no
    span covers it."""
    return _most(self_cover(gap, spans))


def _sweep(gaps: Sequence[Interval], spans: Sequence[Span]
           ) -> Iterator[Tuple[int, List[Span]]]:
    """(index of the gap, the spans that overlap it), gaps in order of start;
    the spans are swept alongside, since gaps may number 10^5."""
    todo = sorted((sp for sp in spans if sp[0] != trace.WINDOW_SPAN),
                  key=lambda sp: sp[1])
    active: List[Span] = []
    k = 0
    for i in sorted(range(len(gaps)), key=lambda i: gaps[i][0]):
        g0, g1 = gaps[i]
        while k < len(todo) and todo[k][1] < g1:
            active.append(todo[k])
            k += 1
        active = [sp for sp in active if sp[2] > g0]
        yield i, active


def reduce(tr: dict, top: int = 10) -> Optional[dict]:
    """`trace.reduce(tr, top)` with `idle_gaps` labelled by `label`, and:

      span_self_s     {span name: seconds}, `self_seconds` over the window
      step_chunk_s    seconds under engine.step_chunk in the window
      idle_by_span_s  {span name: seconds} of device idle (mean over
                      devices), every gap shared out by self coverage, the
                      part under no span to 'harness' (spans that overlap
                      without nesting share the overlap twice)
    """
    red = trace.reduce(tr, top)
    if red is None:
        return None
    lo, hi = trace.window(tr["spans"])
    by_dev: Dict[str, List[Interval]] = defaultdict(list)
    for _, s, e, dev in tr["ops"]:
        by_dev[dev].append((s, e))
    gap_list: List[Interval] = []
    for dev in tr["devices"]:
        gap_list.extend(trace.gaps(trace.union(by_dev.get(dev, []), lo, hi), lo, hi))
    names = [UNCOVERED] * len(gap_list)
    idle: Dict[str, float] = defaultdict(float)
    share = 1e-9 / len(tr["devices"])
    for i, over in _sweep(gap_list, tr["spans"]):
        g0, g1 = gap_list[i]
        cover = self_cover((g0, g1), over)
        names[i] = _most(cover)
        for name, c in cover:
            idle[name] += c * share
        covered = sum(e - s for s, e in trace.union([sp[1:] for sp in over], g0, g1))
        if g1 - g0 > covered:
            idle[UNCOVERED] += (g1 - g0 - covered) * share
    ranked = sorted(zip(names, gap_list), key=lambda g: g[1][1] - g[1][0],
                    reverse=True)
    red["idle_gaps"] = [[n, (e - s) * 1e-9] for n, (s, e) in ranked[:top]]
    red["span_self_s"] = self_seconds(tr["spans"], lo, hi)
    red["step_chunk_s"] = 1e-9 * sum(
        e - s for s, e in trace.union(
            [(s, e) for n, s, e in tr["spans"] if n == STEP], lo, hi))
    red["idle_by_span_s"] = dict(idle)
    return red


def boundary_ms(red: Optional[dict], chunks: int) -> Dict[str, Optional[float]]:
    """The three boundary sums in ms per chunk; None where the trace holds
    no engine span (a program that writes none)."""
    self_s = (red or {}).get("span_self_s") or {}
    if not self_s or chunks <= 0:
        return dict.fromkeys(BOUNDARY)
    return {metric: 1e3 * sum(self_s.get(n, 0.0) for n in names) / chunks
            for metric, names in BOUNDARY.items()}


def notes(red: Optional[dict], chunks: int) -> List[str]:
    """Each engine span's self time and the device idle under each span, in
    ms per chunk, and how much of engine.step_chunk the phases cover."""
    if red is None or chunks <= 0 or not red["span_self_s"]:
        return []

    def per_chunk(d):
        return " ".join(f"{n}={1e3 * t / chunks:.3f}"
                        for n, t in sorted(d.items(), key=lambda kv: -kv[1]))

    step = red["step_chunk_s"]
    phases = sum(t for n, t in red["span_self_s"].items() if n != STEP)
    return [
        f"engine span self time, ms per chunk ({chunks} chunks): "
        + per_chunk(red["span_self_s"]),
        "device idle by span, ms per chunk: " + per_chunk(red["idle_by_span_s"]),
        f"engine phases: {phases!r} s of engine.step_chunk's {step!r} s "
        f"({100.0 * phases / step:.2f}%)" if step > 0 else "no engine.step_chunk",
    ]
