"""One run of one cell: set-up, the measured window, the check, the metrics.

    execute(cell, seed, seconds, trace, rehearse, t_process) -> Result

Set-up builds the deployment from the seed, compiles the plan, warms every
shape the cell's traffic uses and brings the traffic to its steady state:
a first wave whose lengths are spread evenly over one session length, with
the loop kept running until every slot holds a full-length session, so
results come back at a steady pace and the same number of sessions turn
over at each chunk boundary. The window then serves the closed mix for
`seconds`; `session_ticks_per_s` is the ticks of every session whose result
came back in it, over its length.

After the window the program's results are compared with the plain
reference (`benchlib.reference`) twice: a sample of the sessions that came
back in the window, drawn from the seed, from their start; and every lane
over AUDIT_CHUNKS more chunks of the same traffic, each restarted from
the state the engine served (`ReservoirEngine.snapshot_sessions`) and
compared one chunk ahead.

A deployment that learns its readout online (plan.learn "rls", a mix with
targets) is checked on what it serves: its a-priori predictions take the
place of the outputs, and the chunk audit restarts the reference's learner
from a seeded sample of lanes' served (m, P, W): `audit_learn_lanes` in the
cell file, since one P is (N+1)^2 floats. Which gaps a configuration
computes is GAPS[learning]; a limit on any other is refused before the run.
"""

from __future__ import annotations

import dataclasses
import gc
import math
import shutil
import tempfile
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from benchlib import data, reference, traffic
from benchlib import trace as tracelib
from benchlib import registry

AUDIT_CHUNKS = 2  # chunks served after the window and compared one chunk ahead
PROFILE_S = 5.0  # the window's throughput is also noted per this many seconds

# the numbers compared with the reference, for a readout deployment (False)
# and for one that learns its readout online (True)
GAPS = {
    False: ("outputs_gap", "outputs_gap_median", "chunk_states_gap",
            "chunk_outputs_gap"),
    True: ("outputs_gap", "outputs_gap_median", "chunk_states_gap",
           "chunk_weights_gap", "chunk_preds_gap"),
}

_COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "traced",
    "/jax/core/compile/backend_compile_duration": "compiled",
}


class CompileCounter:
    """Counts programs traced and compiled in this process (JAX monitoring)."""

    _instance = None

    def __init__(self):
        self.counts = {"traced": 0, "compiled": 0}
        self.last = 0.0  # host clock of the latest event

    def __call__(self, event, duration, **kwargs):
        key = _COMPILE_EVENTS.get(event)
        if key:
            self.counts[key] += 1
            self.last = time.perf_counter()

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._instance is None:
            import jax

            cls._instance = cls()
            jax.monitoring.register_event_duration_secs_listener(cls._instance)
        return cls._instance

    def snapshot(self) -> Dict[str, int]:
        return dict(self.counts)


class GcClock:
    """Seconds the interpreter spends in garbage collection until stop()."""

    def __init__(self):
        self.total, self._t = 0.0, 0.0
        gc.callbacks.append(self)

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.total += time.perf_counter() - self._t

    def stop(self) -> float:
        gc.callbacks.remove(self)
        return self.total


@dataclasses.dataclass
class Record:
    arrival: traffic.Arrival
    returned: Optional[float] = None  # host clock
    result: object = None


@dataclasses.dataclass
class Lane:
    """A live session at an audit snapshot: its tick, its state m (N, 3),
    its outputs so far, or where it learns its predictions so far, its
    learned W and, on a sampled lane only, its P."""
    t: int
    m: np.ndarray
    outs: Optional[np.ndarray] = None
    preds: Optional[np.ndarray] = None
    wl: Optional[np.ndarray] = None
    p: Optional[np.ndarray] = None


@dataclasses.dataclass
class Result:
    line: dict
    checks: List[Tuple[str, float, float]]
    notes: List[str]
    run: object = None
    sample: Tuple[int, ...] = ()
    gaps: Dict[str, float] = dataclasses.field(default_factory=dict)  # all compared


def apply_rehearsal(cell: registry.Cell) -> None:
    """Shrink a cell to the CPU rehearsal's sizes (bench/rehearsal.json).
    A key of the mix or of the cell file is shrunk only where the cell
    states it, so a readout cell gains no learning key."""
    r = registry.rehearsal()
    spec = cell.config["spec"]
    spec.update(r["spec"], n=min(int(spec["n"]), int(r["spec"]["n"])))
    cell.config["plan"].update(r["plan"])
    cell.config["readout"].update(r["readout"])
    cell.traffic.update({k: v for k, v in r["traffic"].get(cell.traffic["kind"], {}).items()
                         if k in cell.traffic})
    cell.check.update({k: v for k, v in r["check"].items() if k in cell.check})


def validate(cell: registry.Cell) -> bool:
    """Refuse, before any run, a cell that its deployment cannot run as
    stated or whose limits name a gap the deployment does not compute.
    Returns whether the deployment learns."""
    from benchlib import system

    mix = traffic.validate(dict(cell.traffic))
    system.validate_deployment(cell.config, mix, cell.chips)
    learning = "targets" in mix
    unknown = sorted(set(cell.check["limits"]) - set(GAPS[learning]))
    if unknown:
        raise ValueError(f"cell {cell.name}: limits on {unknown}, which a "
                         f"{'learning' if learning else 'readout'} deployment does "
                         f"not compute; it computes {list(GAPS[learning])}")
    if learning:
        if int(cell.check.get("audit_learn_lanes", 0)) < 1:
            raise ValueError(f"cell {cell.name}: a learning cell states "
                             "audit_learn_lanes >= 1")
        if int(cell.check["horizon_ticks"]) <= traffic.washout(mix):
            raise ValueError(f"cell {cell.name}: horizon_ticks "
                             f"{cell.check['horizon_ticks']} does not pass "
                             f"learn_washout {traffic.washout(mix)}, so no "
                             "learned prediction would be compared")
    return learning


class Run:
    def __init__(self, cell, seed, seconds, trace, rehearse, t_process):
        from benchlib import system

        self.sys = system
        self.cell = cell
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.rehearse = bool(rehearse)
        self.t_process = t_process
        self.cfg = cell.config
        self.learning = validate(cell)
        self.mix = dict(cell.traffic)
        self.washout = traffic.washout(self.mix)
        self.e = int(self.cfg["plan"]["ensemble"])
        self.k = int(self.cfg["plan"]["chunk_ticks"])
        self.n_in = int(self.cfg["spec"]["n_in"])
        self.records: Dict[int, Record] = {}
        self.trace_launches = 0
        self.notes: List[str] = []
        self.audit_chunks: List[Tuple[dict, dict]] = []
        self._tracing = None
        self._trace_dir = None
        self._feeding = False  # closed loop: a new session per result

    # -- traffic ----------------------------------------------------------

    def _submit(self, arrival: traffic.Arrival):
        u = traffic.inputs(self.mix, self.seed, arrival, self.n_in)
        if self.learning:
            session = self.sys.session(arrival.sid, u, None,
                                       targets=traffic.targets(self.mix, u),
                                       washout=self.washout)
        else:
            pool = self.data["readouts"]
            readout = pool[traffic.readout_index(arrival, pool.shape[0])]
            session = self.sys.session(arrival.sid, u, readout)
        self.eng.submit(session)
        self.records[arrival.sid] = Record(arrival)

    def _step(self) -> bool:
        import jax

        with jax.profiler.TraceAnnotation("step_chunk"):
            progressed = self.eng.step_chunk()
        if self._tracing is not None and progressed:
            self.trace_launches += 1
        self._collect()
        return progressed

    def _collect(self) -> None:
        import jax

        t = time.perf_counter()
        for sid, res in self.eng.pop_results().items():
            rec = self.records.get(sid)
            if rec is not None:
                rec.returned, rec.result = t, res
                if self._feeding:
                    with jax.profiler.TraceAnnotation("submit"):
                        self._submit(self._closed.next())

    # -- profiler ---------------------------------------------------------

    def _trace_start(self):
        import jax

        self._trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(self._trace_dir)
        self._tracing = jax.profiler.TraceAnnotation(tracelib.WINDOW_SPAN)
        self._tracing.__enter__()
        self.trace_launches = 0

    def _trace_stop(self):
        import jax

        self._tracing.__exit__(None, None, None)
        self._tracing = None
        jax.profiler.stop_trace()

    # -- set-up -----------------------------------------------------------

    def setup(self):
        self.data = data.make(self.cfg, self.seed)
        t0 = time.perf_counter()
        self.eng = self.sys.engine(self.cfg, self.data, chips=self.cell.chips,
                                   interpret=self.rehearse)
        self._warm_shapes()
        self.compile_s = time.perf_counter() - t0
        self._closed_ramp()

    def _warm_shapes(self):
        """One full turnover of every slot, through the public path: E
        one-chunk sessions admitted and retired together."""
        for j in range(self.e):
            self._submit(traffic.Arrival(-1 - j, self.k))
        while self._step():
            pass
        self.records.clear()

    def _closed_ramp(self):
        """First wave: session j runs K * ceil(L/K * (j+1)/E) ticks, so the
        same number of lanes frees at every boundary; the loop then runs
        until the last of the wave has come back."""
        self._closed = traffic.Closed(self.mix)
        self._feeding = True
        chunks = int(self.mix["session_ticks"]) // self.k
        for j in range(self.e):
            a = self._closed.next()
            a.ticks = self.k * int(math.ceil(chunks * (j + 1) / self.e))
            self._submit(a)
        for _ in range(self.e * (traffic.IN_FLIGHT_PER_SLOT - 1)):
            self._submit(self._closed.next())
        wave = set(range(self.e))
        while any(self.records[s].returned is None for s in wave):
            self._step()

    # -- window -----------------------------------------------------------

    def window(self):
        # sessions that came back during set-up are owed nothing
        self.records = {s: r for s, r in self.records.items() if r.returned is None}
        self.compiles_before = CompileCounter.get().snapshot()
        t_s = time.perf_counter()
        self.t_window = t_s
        self.setup_s = t_s - self.t_process
        end = t_s + self.seconds
        gc_watch = GcClock()
        span = float(self.cell.check["trace_seconds"])
        trace_at = t_s + max(0.0, (self.seconds - span) / 2) if self.trace else math.inf
        while True:
            now = time.perf_counter()
            if now >= end:
                break
            if self._tracing is None and now >= trace_at:
                trace_at = math.inf
                self._trace_start()
                trace_end = now + span
            elif self._tracing is not None and now >= trace_end:
                self._trace_stop()
            self._step()
        self.t_end = time.perf_counter()
        self.gc_s = gc_watch.stop()
        if self._tracing is not None:
            self._trace_stop()
        self.compiles_after = CompileCounter.get().snapshot()

    def audit(self):
        """After the window: AUDIT_CHUNKS more chunks of the same traffic,
        the pipeline drained before each, and every live session's state
        read (`snapshot_sessions`), so that the check can restart the
        reference from each lane's served state and compare one chunk
        ahead. Untimed; the closed loop keeps every slot busy.

        Where sessions learn, each snapshot before a chunk keeps the P of
        `audit_learn_lanes` lanes drawn from the seed and drops the rest
        at once; the last snapshot keeps none."""
        picker = data.rng(self.seed, data.STREAM_AUDIT)

        def snapshot(keep_p: bool):
            ckpts = [c for c in self.eng.snapshot_sessions() if c.m is not None]
            kept = set()
            if self.learning and keep_p and ckpts:
                sids = sorted(c.sid for c in ckpts)
                k = min(int(self.cell.check["audit_learn_lanes"]), len(sids))
                kept = {sids[i] for i in picker.choice(len(sids), size=k, replace=False)}
            snap = {c.sid: Lane(c.t, c.m, c.outs, c.preds, c.Wl,
                                c.P if c.sid in kept else None) for c in ckpts}
            del ckpts
            self._collect()
            return snap

        after = snapshot(True)
        for i in range(AUDIT_CHUNKS):
            before = after
            self._step()
            after = snapshot(i + 1 < AUDIT_CHUNKS)
            self.audit_chunks.append((before, after))
        self._feeding = False

    # -- memory, reference, metrics ----------------------------------------

    def memory_peak(self) -> Optional[int]:
        import jax

        peaks = []
        for d in jax.local_devices()[: self.cell.chips]:
            stats = d.memory_stats() or {}
            if "peak_bytes_in_use" in stats:
                peaks.append(int(stats["peak_bytes_in_use"]))
        return max(peaks) if peaks else None

    def impl(self) -> str:
        return self.sys.impl(self.eng)

    def release(self):
        self.eng = None
        gc.collect()


# ---------------------------------------------------------------------------
# the check against the plain reference
# ---------------------------------------------------------------------------


def _gap(a, b) -> float:
    if a is None or b is None or np.shape(a) != np.shape(b):
        return math.inf
    a = np.asarray(a, np.float64)
    if not np.isfinite(a).all():
        return math.inf
    return float(np.max(np.abs(a - np.asarray(b, np.float64)))) if a.size else 0.0


def sample(run: Run) -> List[int]:
    """The sessions compared from their start: drawn from the seed among
    those whose results came back in the window."""
    pool = sorted(s for s, r in run.records.items()
                  if r.returned is not None and run.t_window <= r.returned <= run.t_end)
    k = int(run.cell.check["sample_sessions"])
    if len(pool) <= k:
        return pool
    picked = data.rng(run.seed, data.STREAM_SAMPLE).choice(len(pool), size=k, replace=False)
    return sorted(pool[i] for i in picked)


def _readout(run: Run, a: traffic.Arrival) -> np.ndarray:
    pool = run.data["readouts"]
    return pool[traffic.readout_index(a, pool.shape[0])]


def _ok(res) -> bool:
    return res is not None and getattr(res, "error", None) is None


def _learner(run: Run, targets, skip, p0=None, w0=None) -> dict:
    plan = run.cfg["plan"]
    return {"lam": float(plan["learn_lam"]), "reg": float(plan["learn_reg"]),
            "targets": targets, "skip": skip, "p0": p0, "w0": w0}


def compare(run: Run, sids: List[int]) -> Dict[str, float]:
    """The sampled sessions from their start, over their first
    `horizon_ticks` ticks (PERF.md, section 2):

      outputs_gap         widest |served - reference| output; a float32
                          trajectory of a chaotic reservoir decorrelates from
                          any other within some tens of ticks, so the horizon
                          is short where N is large
      outputs_gap_median  the median session's widest gap: steadier from
                          seed to seed than the widest

    Where sessions learn, the outputs compared are the a-priori
    predictions, against the reference's learner from P = I / reg, W = 0.
    """
    h = int(run.cell.check["horizon_ticks"])
    arrivals = [run.records[s].arrival for s in sids]
    full = [traffic.inputs(run.mix, run.seed, a, run.n_in) for a in arrivals]
    ins = [u[: min(a.ticks, h)] for u, a in zip(full, arrivals)]
    block = int(run.cell.check["reference_block"])
    if run.learning:
        learner = _learner(run, [traffic.targets(run.mix, u)[: len(x)]
                                 for u, x in zip(full, ins)],
                           [run.washout] * len(ins))
        ref = reference.drive(run.data, ins, [len(u) for u in ins],
                              block=block, learner=learner)
        key = "predictions"
    else:
        ref = reference.drive(run.data, ins, [len(u) for u in ins],
                              [_readout(run, a) for a in arrivals], block=block)
        key = "outputs"
    out = {"outputs_gap": 0.0}
    per_session = []
    for s, a, want in zip(sids, arrivals, ref):
        res = run.records[s].result
        x = getattr(res, key) if _ok(res) else None
        ok = x is not None and np.shape(x)[0] == a.ticks
        gap = _gap(x[: min(a.ticks, h)] if ok else None, want[key])
        per_session.append(gap)
        out["outputs_gap"] = max(out["outputs_gap"], gap)
    out["outputs_gap_median"] = float(np.median(per_session)) if per_session else 0.0
    return out


def _weights_gap(served, want) -> float:
    """Widest |served - reference| learned weight over the reference's widest
    weight of that lane (0 where both are all zero)."""
    if served is None or np.shape(served) != np.shape(want):
        return math.inf
    scale = float(np.max(np.abs(want))) if np.size(want) else 0.0
    gap = _gap(served, want)
    if gap == 0.0:
        return 0.0
    return gap / scale if scale > 0.0 else math.inf


def _served_after(run: Run, sid: int, after: Dict[int, Lane]):
    """(t1, m, outputs, predictions, learned W) of a lane after an audit
    chunk; a session that finished in the chunk is held to its result."""
    if sid in after:
        lane = after[sid]
        return lane.t, lane.m, lane.outs, lane.preds, lane.wl
    res = run.records[sid].result
    t1 = run.records[sid].arrival.ticks
    if not _ok(res):
        return t1, None, None, None, None
    wl = None if res.learned_readout is None else np.asarray(res.learned_readout.w_out)
    return t1, res.final_m, res.outputs, res.predictions, wl


def compare_audit(run: Run) -> Dict[str, float]:
    """Every lane of each audit chunk, one chunk ahead of its served state:

      chunk_states_gap    widest |served - reference| state (all of m) at
                          the end of the chunk, the reference restarted
                          from the state the engine served before it (a
                          session admitted in the chunk, from its start)
      chunk_outputs_gap   widest output gap over the chunk's ticks

    Where sessions learn, on the lanes whose P was kept before the chunk,
    the reference's learner restarted from their served (m, P, W):

      chunk_weights_gap   widest learned-weight gap after the chunk, over
                          the reference's widest weight of the lane
      chunk_preds_gap     widest prediction gap over the chunk's ticks

    A session that finished in the chunk is held to its result."""
    starts, ins, lens, readouts, served, learn = [], [], [], [], [], []
    for before, after in run.audit_chunks:
        for sid in sorted(set(before) | set(after)):
            a = run.records[sid].arrival
            lane = before.get(sid)
            t0, m0 = (lane.t, lane.m) if lane is not None else (0, None)
            t1, m1, outs, preds, wl = _served_after(run, sid, after)
            if t1 <= t0:
                continue
            u = traffic.inputs(run.mix, run.seed, a, run.n_in)
            starts.append(m0)
            ins.append(u[t0:t1])
            lens.append(t1 - t0)
            if run.learning:
                served.append((m1, None))
                if lane is not None and lane.p is not None:
                    ok = preds is not None and np.shape(preds)[0] >= t1
                    learn.append({
                        "start": m0, "u": u[t0:t1], "p0": lane.p, "w0": lane.wl,
                        "targets": traffic.targets(run.mix, u)[t0:t1],
                        "skip": max(0, run.washout - t0),
                        "wl": wl, "preds": preds[t0:t1] if ok else None})
            else:
                readouts.append(_readout(run, a))
                ok = outs is not None and np.shape(outs)[0] >= t1
                served.append((m1, outs[t0:t1] if ok else None))
    names = GAPS[run.learning][2:]
    out = dict.fromkeys(names, 0.0)
    if not served or (run.learning and not learn):
        return dict.fromkeys(names, math.inf)
    block = int(run.cell.check["reference_block"])
    ref = reference.drive(run.data, ins, lens, None if run.learning else readouts,
                          starts=starts, block=block)
    for (m1, outs), want in zip(served, ref):
        out["chunk_states_gap"] = max(out["chunk_states_gap"], _gap(m1, want["final_m"]))
        if not run.learning:
            out["chunk_outputs_gap"] = max(out["chunk_outputs_gap"],
                                           _gap(outs, want["outputs"]))
    del ref
    if run.learning:
        ref = reference.drive(
            run.data, [r["u"] for r in learn], [len(r["u"]) for r in learn],
            starts=[r["start"] for r in learn], block=block,
            learner=_learner(run, [r["targets"] for r in learn],
                             [r["skip"] for r in learn],
                             [r["p0"] for r in learn], [r["w0"] for r in learn]))
        for r, want in zip(learn, ref):
            out["chunk_weights_gap"] = max(out["chunk_weights_gap"],
                                           _weights_gap(r["wl"], want["final_w"]))
            out["chunk_preds_gap"] = max(out["chunk_preds_gap"],
                                         _gap(r["preds"], want["predictions"]))
        run.notes.append(f"audit: {len(learn)} learning lane-chunks restarted "
                         "from their served P and W")
    run.notes.append(f"audit: {len(served)} lane-chunks compared one chunk ahead")
    return out


def checks(run: Run, gaps: Dict[str, float], failed: int) -> List[Tuple[str, float, float]]:
    limits = run.cell.check["limits"]
    out = [(name, gaps[name], float(limits[name])) for name in sorted(limits)]
    out.append(("failed", float(failed), 0.0))
    return out


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------


def end_to_end(run: Run) -> Tuple[Dict[str, float], int, int]:
    """(values, attempted, failed) from the harness's own clock."""
    back = [r for r in run.records.values()
            if r.returned is not None and run.t_window <= r.returned <= run.t_end]
    ticks = sum(r.arrival.ticks for r in back)
    vals = {"setup_s": run.setup_s,
            "session_ticks_per_s": ticks / (run.t_end - run.t_window)}
    failed = sum(1 for r in back if not _ok(r.result))
    bins = np.zeros(int(math.ceil(run.seconds / PROFILE_S)))
    for r in back:
        bins[min(int((r.returned - run.t_window) / PROFILE_S), len(bins) - 1)] += r.arrival.ticks
    widths = np.minimum(PROFILE_S, run.seconds - PROFILE_S * np.arange(len(bins)))
    run.notes.append(f"session-ticks/s in each {PROFILE_S:g} s of the window: "
                     + " ".join(f"{v:.0f}" for v in bins / widths)
                     + f"; garbage collection in the window: {run.gc_s:.3f} s")
    return vals, len(back), failed


class Context:
    """What a per-layer metric's reader may read (bench/metrics/*.py)."""

    def __init__(self, run: Run, reduced: Optional[dict], peak: Optional[dict]):
        self.trace = reduced  # benchlib.trace.reduce output, or None
        self.chunks_in_trace = run.trace_launches
        self.peak = peak
        self.compile_s = run.compile_s
        self.shape = {
            "n": int(run.cfg["spec"]["n"]), "e": run.e, "k": run.k,
            "hold_steps": int(run.cfg["spec"]["hold_steps"]),
            "stages": 4, "n_in": run.n_in,
            "n_out": int(run.cfg["readout"]["n_out"]),
            "itemsize": 4,
            "collect_states": False,
            "chips": run.cell.chips,
            "learn": run.cfg["plan"].get("learn"),
            "s": int(run.cfg["spec"]["n"]) + 1,
        }
        self.records = run.records
        self.window = (run.t_window, run.t_end)
        self.notes = run.notes


# ---------------------------------------------------------------------------
# the whole run
# ---------------------------------------------------------------------------


def _finite(x: float) -> float:
    return x if math.isfinite(x) else 1e30


def execute(cell: registry.Cell, seed: int, seconds: float, trace: bool,
            rehearse: bool, t_process: float) -> Result:
    import jax

    devices = jax.local_devices()
    run = Run(cell, seed, seconds, trace, rehearse, t_process)
    run.setup()
    run.window()
    peak_mem = run.memory_peak()
    run.audit()
    impl = run.impl()
    values, attempted, failed = end_to_end(run)
    compiles = {k: run.compiles_after[k] - run.compiles_before[k]
                for k in run.compiles_after}
    run.notes.append(
        f"impl={impl} compile_s={run.compile_s:.3f} setup_s={run.setup_s:.3f} "
        f"programs traced in the window={compiles['traced']} "
        f"compiled in the window={compiles['compiled']}")
    reduced = None
    if trace and run._trace_dir is not None:
        try:
            reduced = tracelib.reduce(tracelib.load(tracelib.find_xplane(run._trace_dir)))
        finally:
            shutil.rmtree(run._trace_dir, ignore_errors=True)
    run.release()
    sids = sample(run)
    gaps = (compare(run, sids) if sids
            else dict.fromkeys(cell.check["limits"], math.inf))
    gaps.update(compare_audit(run))
    run.notes.append(f"compared {len(sids)} sessions against the reference")
    chk = checks(run, gaps, failed)
    correct = bool(sids) and all(v <= lim for _, v, lim in chk)
    if not sids:
        run.notes.append("no session to compare: the window owed none")
    kind = devices[0].device_kind
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": peak_mem}
    if trace:
        peak = None
        if not rehearse:
            peaks = registry.peaks()
            if kind not in peaks:
                raise KeyError(f"device_kind {kind!r} is not in bench/peaks.json")
            peak = peaks[kind]
        ctx = Context(run, reduced, peak)
        metrics = {}
        units = {m["name"]: m["unit"] for m in cell.per_layer}
        for name, read in cell.readers().items():
            v = read(ctx)
            if v is not None:
                metrics[name] = {"value": v, "unit": units[name]}
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        metrics = {name: {"value": values[name], "unit": units[name]}
                   for name in units if name in values}
    line = {"correct": correct, "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": device}
    if trace and reduced is not None:
        line["breakdown"] = {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
    line["checks"] = {name: {"value": _finite(v), "limit": lim} for name, v, lim in chk}
    return Result(line, chk, run.notes, run, tuple(sids), gaps)
