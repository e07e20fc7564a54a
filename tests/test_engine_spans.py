"""The engine's phase spans (`serve/reservoir.py`, `_span`): one span per
phase of a chunk boundary, in the order the boundary runs them, and as many
at E=256 as at E=64 — never one per session or per lane."""

from collections import Counter

import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import ExecPlan, compile_plan, make_spec
from repro.core.reservoir import Readout
from repro.serve import reservoir
from repro.serve.reservoir import ReservoirEngine, StreamSession

N, K, N_OUT = 4, 4, 2
PHASES = ("engine.retire", "engine.admit", "engine.assemble", "engine.launch",
          "engine.harvest", "engine.fetch", "engine.nan_guard",
          "engine.finalize")


class _Recorder:
    """Stands in for jax.profiler.TraceAnnotation: logs entries and exits."""

    log = []

    def __init__(self, name, **kwargs):
        self.name = name

    def __enter__(self):
        self.log.append(("enter", self.name))
        return self

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))
        return False


def _closed_turnover(monkeypatch, e):
    """Closed turnover at E slots: 3E sessions, the first E of K and 2K
    ticks in turn and the rest of 2K, so lanes retire and refill at most
    boundaries. Returns the recorded span events of each step_chunk call."""
    log = []
    monkeypatch.setattr(_Recorder, "log", log)
    monkeypatch.setattr(reservoir, "_span", _Recorder)
    spec = make_spec(n=N, n_in=1, hold_steps=2, dtype=jnp.float32)
    eng = ReservoirEngine(
        compile_plan(spec, ExecPlan(impl="scan", ensemble=e, chunk_ticks=K)),
        n_out=N_OUT,
    )
    rng = np.random.default_rng(0)
    w = rng.standard_normal((N + 1, N_OUT)).astype(np.float32)
    for sid in range(3 * e):
        ticks = 2 * K if sid % 2 == 0 or sid >= e else K
        u = rng.uniform(0.0, 0.5, (ticks, 1)).astype(np.float32)
        eng.submit(StreamSession(sid=sid, u_seq=u,
                                 readout=Readout(w_out=w, washout=0)))
    calls = []
    while True:
        start = len(log)
        more = eng.step_chunk()
        calls.append(log[start:])
        if not more:
            break
    assert len(eng.pop_results()) == 3 * e
    return calls


@pytest.mark.parametrize("e", [64, 256])
def test_one_span_per_phase_in_boundary_order(monkeypatch, e):
    calls = _closed_turnover(monkeypatch, e)
    full = 0
    for events in calls:
        entered = [name for kind, name in events if kind == "enter"]
        assert entered[0] == "engine.step_chunk"
        assert events[-1] == ("exit", "engine.step_chunk")
        phases = entered[1:]
        assert max(Counter(entered).values()) == 1, entered
        assert phases == [p for p in PHASES if p in phases], phases
        assert len(entered) <= 9
        if len(phases) == len(PHASES):
            full += 1
            # fetch and nan_guard nest in harvest; finalize follows it
            i = events.index(("enter", "engine.harvest"))
            j = events.index(("exit", "engine.harvest"))
            inner = [name for _, name in events[i + 1: j]]
            assert inner == ["engine.fetch", "engine.fetch",
                             "engine.nan_guard", "engine.nan_guard"]
            assert events[j + 1] == ("enter", "engine.finalize")
    assert full >= 2  # boundaries that retired, admitted and harvested


def test_spans_per_step_chunk_do_not_depend_on_e(monkeypatch):
    counts = {
        e: [sum(kind == "enter" for kind, _ in events)
            for events in _closed_turnover(monkeypatch, e)]
        for e in (64, 256)
    }
    assert counts[64] == counts[256]
    assert max(counts[64]) == 9
