"""Everything a run feeds the system, made from `--seed`.

The coupling matrix, input matrix, initial state and parameters of the
deployment (the reservoir's "weights"), each session's input series and the
readouts. Made here, in bulk on the host, so the plain reference and the
program see the same numbers; the program gets them as its inputs.

Seeds are any non-negative whole number (numpy's SeedSequence takes more
than 64 bits). Each use draws from its own stream, keyed by a constant.
"""

from __future__ import annotations

import math

import numpy as np

STREAM_W, STREAM_W_IN, STREAM_READOUT, STREAM_SAMPLE, STREAM_AUDIT = 0, 1, 2, 4, 5
_MASK64 = np.uint64(0xFFFFFFFFFFFFFFFF)


def rng(seed: int, stream: int) -> np.random.Generator:
    if seed < 0:
        raise ValueError(f"seed must be a non-negative whole number; got {seed}")
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def coupling_matrix(n: int, seed: int, target_rho: float) -> np.ndarray:
    """Paper 3.1: zero diagonal, off-diagonal U[-1, 1], scaled to rho(W)."""
    w = rng(seed, STREAM_W).uniform(-1.0, 1.0, size=(n, n))
    np.fill_diagonal(w, 0.0)
    if n > 1:
        w *= target_rho / float(np.max(np.abs(np.linalg.eigvals(w))))
    return w.astype(np.float32)


def initial_state(n: int, phi0_deg: float) -> np.ndarray:
    """Paper Eq. 4: the same unit vector for every oscillator, (N, 3)."""
    phi = math.radians(phi0_deg)
    m = np.array(
        [math.sin(phi) * math.cos(phi), math.sin(phi) ** 2, math.cos(phi)],
        np.float32,
    )
    return np.broadcast_to(m, (n, 3)).copy()


def make(cfg: dict, seed: int) -> dict:
    """The deployment's data for one run: w (N, N), w_in (N, n_in), m0 (N, 3),
    params (Table 1), dt, hold_steps, and a pool of (N+1, n_out) readouts."""
    s = cfg["spec"]
    n, n_in = int(s["n"]), int(s["n_in"])
    n_out = int(cfg["readout"]["n_out"])
    pool = int(cfg["readout"]["pool"])
    w_in = rng(seed, STREAM_W_IN).uniform(-1.0, 1.0, size=(n, n_in))
    scale = math.sqrt(3.0 / (n + 1))  # unit variance per output for |x| ~ 1
    readouts = rng(seed, STREAM_READOUT).uniform(
        -scale, scale, size=(pool, n + 1, n_out)
    ).astype(np.float32)
    return {
        "w": coupling_matrix(n, seed, float(s["target_rho"])),
        "w_in": w_in.astype(np.float32),
        "m0": initial_state(n, float(s["phi0_deg"])),
        "params": dict(s["params"]),
        "dt": float(s["dt"]),
        "hold_steps": int(s["hold_steps"]),
        "readouts": readouts,
    }


def _splitmix64(x: np.ndarray) -> np.ndarray:
    x = (x + np.uint64(0x9E3779B97F4A7C15)) & _MASK64
    x = ((x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)) & _MASK64
    x = ((x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)) & _MASK64
    return x ^ (x >> np.uint64(31))


def session_inputs(seed: int, sid: int, ticks: int, n_in: int,
                   low: float, high: float) -> np.ndarray:
    """Session `sid`'s (ticks, n_in) input series, uniform on [low, high).

    A counter-based hash of (seed, sid, position), so any session's series
    can be made again after the window without keeping it."""
    base = _splitmix64(np.array([int(seed) & 0xFFFFFFFFFFFFFFFF], np.uint64))
    base = _splitmix64(base ^ np.uint64(int(sid) & 0xFFFFFFFFFFFFFFFF))
    pos = np.arange(ticks * n_in, dtype=np.uint64)
    with np.errstate(over="ignore"):
        bits = _splitmix64(base + pos)
    unit = (bits >> np.uint64(40)).astype(np.float64) / float(1 << 24)
    return (low + (high - low) * unit).astype(np.float32).reshape(ticks, n_in)
