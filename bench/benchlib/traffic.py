"""The one traffic generator. Every mix is a data file under `bench/traffic/`.

One kind of mix so far:

closed  every slot stays busy: IN_FLIGHT_PER_SLOT x E sessions are kept
        submitted, and a new one goes in as each result comes back, so the
        engine's queue is never empty at a boundary. Every session has
        `session_ticks` ticks and returns readout outputs (a seeded
        (N+1, n_out) readout from the deployment's pool).

Inputs are `benchlib.data.session_inputs`, uniform on [input_low,
input_high).
"""

from __future__ import annotations

import dataclasses

import numpy as np

from benchlib import data

KINDS = ("closed",)
NEEDS = ("session_ticks", "input_low", "input_high")
IN_FLIGHT_PER_SLOT = 2


@dataclasses.dataclass
class Arrival:
    sid: int
    ticks: int


def validate(mix: dict) -> dict:
    kind = mix.get("kind")
    if kind not in KINDS:
        raise ValueError(f"traffic kind must be one of {KINDS}; got {kind!r}")
    missing = [k for k in NEEDS if k not in mix]
    if missing:
        raise ValueError(f"traffic file lacks {missing}")
    return mix


class Closed:
    """Session factory for the closed mix: sids 0, 1, 2, ... in order."""

    def __init__(self, mix: dict):
        self.ticks = int(mix["session_ticks"])
        self.next_sid = 0

    def next(self) -> Arrival:
        sid = self.next_sid
        self.next_sid += 1
        return Arrival(sid, self.ticks)


def inputs(mix: dict, seed: int, arrival: Arrival, n_in: int) -> np.ndarray:
    return data.session_inputs(
        seed, arrival.sid, arrival.ticks, n_in,
        float(mix["input_low"]), float(mix["input_high"]),
    )


def readout_index(arrival: Arrival, pool: int) -> int:
    return arrival.sid % pool
