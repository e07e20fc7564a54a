"""The one traffic generator. Every mix is a data file under `bench/traffic/`.

One kind of mix so far:

closed  every slot stays busy: IN_FLIGHT_PER_SLOT x E sessions are kept
        submitted, and a new one goes in as each result comes back, so the
        engine's queue is never empty at a boundary. Every session has
        `session_ticks` ticks and returns readout outputs (a seeded
        (N+1, n_out) readout from the deployment's pool), or, where the
        mix names `targets`, learns its readout online against them and
        returns its a-priori predictions (no update in its first
        `learn_washout` ticks).

Inputs are `benchlib.data.session_inputs`, uniform on [input_low,
input_high). Targets are made from each session's own inputs by the
recurrence the mix names (TARGETS), here, importing nothing of the program.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from benchlib import data

KINDS = ("closed",)
NEEDS = ("session_ticks", "input_low", "input_high")
IN_FLIGHT_PER_SLOT = 2
NARMA_ORDER = 10


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
    if "targets" in mix and mix["targets"] not in TARGETS:
        raise ValueError(f"traffic targets must be one of {sorted(TARGETS)}; "
                         f"got {mix['targets']!r}")
    if "learn_washout" in mix:
        if "targets" not in mix:
            raise ValueError("traffic learn_washout is set but the mix has no targets")
        washout = mix["learn_washout"]
        if not isinstance(washout, int) or isinstance(washout, bool) or not (
                0 <= washout < int(mix["session_ticks"])):
            raise ValueError("traffic learn_washout must be a whole number in "
                             f"[0, session_ticks); got {washout!r}")
    return mix


def narma10(u: np.ndarray) -> np.ndarray:
    """NARMA-10 targets of a (T, 1) input series, from y = 0:

        y[t+1] = 0.3 y[t] + 0.05 y[t] (y[t] + ... + y[t-9])
                 + 1.5 u[t-9] u[t] + 0.1,    targets[t] = y[t+1]

    with y and u taken as 0 before the series starts. Computed in float64;
    a series that is not finite raises."""
    if u.ndim != 2 or u.shape[1] != 1:
        raise ValueError(f"narma10 needs a (T, 1) input series; got {u.shape}")
    x = [float(v) for v in u[:, 0]]
    out = np.empty(len(x))
    hist = [0.0] * NARMA_ORDER  # y[t-9] ... y[t], oldest first
    y = 0.0
    for t, u_t in enumerate(x):
        u_lag = x[t - NARMA_ORDER + 1] if t >= NARMA_ORDER - 1 else 0.0
        y = 0.3 * y + 0.05 * y * sum(hist) + 1.5 * u_lag * u_t + 0.1
        out[t] = y
        hist.pop(0)
        hist.append(y)
    if not np.isfinite(out).all():
        raise ValueError("narma10 targets are not finite for this input series")
    return out.astype(np.float32)[:, None]


TARGETS = {"narma10": narma10}


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


def targets(mix: dict, u: np.ndarray) -> np.ndarray:
    """A learning session's (T, n_out) targets from its own inputs."""
    return TARGETS[mix["targets"]](u)


def washout(mix: dict) -> int:
    return int(mix.get("learn_washout", 0))


def readout_index(arrival: Arrival, pool: int) -> int:
    return arrival.sid % pool
