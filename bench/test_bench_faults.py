"""The check fails a broken timed path.

Each test drives a whole run of a cell on the CPU at the rehearsal sizes
(everything but the look for a chip), with the program broken underneath,
and sees `correct` come out false; the sound program comes out true. The
faults are those a served chunk can have: a step that returns its state
unchanged, half of the batch left out, an answer altered where it is
produced, and an admission that also writes into a live lane mid-session.
(The cells run on one chip, so there is no exchange between chips to leave
out.)"""

import os
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from benchlib import harness, registry  # noqa: E402
from benchlib import system  # noqa: E402,F401  (puts the program on the path)

import jax.numpy as jnp  # noqa: E402
from repro.api.compiled import CompiledSim  # noqa: E402
import repro.serve.reservoir as served  # noqa: E402
from repro.serve.state_store import SlotStore  # noqa: E402

CELLS = ("n1.readout_closed", "n1000.readout_closed")


def state_unchanged(orig):
    def tick_chunk(self, m_planes, u_block, *args, **kwargs):
        _, states = orig(self, m_planes, u_block, *args, **kwargs)
        return m_planes, jnp.broadcast_to(m_planes[0][None], states.shape)
    return tick_chunk


def half_batch_left_out(orig):
    def tick_chunk(self, m_planes, u_block, *args, **kwargs):
        m_new, states = orig(self, m_planes, u_block, *args, **kwargs)
        served_lanes = jnp.arange(m_planes.shape[-1]) % 2 == 0  # every other lane
        return (jnp.where(served_lanes, m_new, m_planes),
                jnp.where(served_lanes, states, m_planes[0][None]))
    return tick_chunk


def answer_altered(orig):
    def apply_readouts_chunk(states_block, w_out):
        return orig(states_block, w_out).at[0].add(0.01)
    return apply_readouts_chunk


def live_lane_overwritten(orig):
    def admit_many(self, items):
        orig(self, items)
        admitted = {item[0] for item in items}
        live = [s for s in range(self.num_slots) if self._active[s] and s not in admitted]
        if items and live:  # the lowest live lane restarts from the template
            self.m = self.m.at[:, :, live[0]].set(self._m0_col)
    return admit_many


FAULTS = {
    "state_unchanged": (CompiledSim, "tick_chunk", state_unchanged),
    "half_batch_left_out": (CompiledSim, "tick_chunk", half_batch_left_out),
    "answer_altered": (served, "_apply_readouts_chunk", answer_altered),
    "live_lane_overwritten": (SlotStore, "admit_many", live_lane_overwritten),
}


def run_cell(name, seed):
    cell = registry.Cell(name)
    harness.apply_rehearsal(cell)
    return harness.execute(cell, seed, 0.6, False, True, time.perf_counter())


@pytest.mark.parametrize("cell", CELLS)
def test_sound_program_is_correct(cell):
    res = run_cell(cell, 21)
    assert res.sample, res.notes
    assert res.line["correct"], res.checks


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_broken_timed_path_is_not_correct(cell, fault, monkeypatch):
    owner, attr, make = FAULTS[fault]
    monkeypatch.setattr(owner, attr, make(getattr(owner, attr)))
    res = run_cell(cell, 22)
    assert res.sample, res.notes
    assert not res.line["correct"], res.checks
    assert any(v > lim for _, v, lim in res.checks)
