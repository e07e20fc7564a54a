"""The system under test, as the benchmark sees it.

Everything the benchmark takes from the program goes through here: the
deployment is handed to `compile_plan` as a `SimSpec` built from the
benchmark's own data, and the engine is driven through its public surface
only (`submit`, `step_chunk`, `pop_results`, and after the window
`snapshot_sessions`).
"""

from __future__ import annotations

import os
import sys

from benchlib.registry import ROOT

if os.path.join(ROOT, "src") not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, "src"))

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.api import ExecPlan, SimSpec, compile_plan  # noqa: E402
from repro.api.cache import enable_persistent_cache  # noqa: E402,F401
from repro.core.constants import STOParams  # noqa: E402
from repro.core.reservoir import Readout  # noqa: E402
from repro.serve.reservoir import ReservoirEngine, StreamSession  # noqa: E402


# What the harness wires through to the program, and the values it allows
# for the rest: a configuration that asks for more fails before any run
# instead of running something else.
SPEC_KEYS = {"n", "n_in", "hold_steps", "dt", "target_rho", "phi0_deg", "params"}
SPEC_FIXED = {"tableau": "rk4", "dtype": "float32"}
PLAN_KEYS = {"impl", "ensemble", "chunk_ticks", "precision"}
PLAN_FIXED = {"learn": None, "autoscale": None}


def validate(cfg: dict) -> dict:
    """Refuse a configuration key that this harness does not run as stated."""
    for group, keys, fixed in (("spec", SPEC_KEYS, SPEC_FIXED),
                               ("plan", PLAN_KEYS, PLAN_FIXED)):
        for key, value in cfg[group].items():
            if key in keys:
                continue
            if key not in fixed:
                raise ValueError(f"configuration {group}.{key} is not wired through")
            if value != fixed[key]:
                raise ValueError(f"configuration {group}.{key}={value!r} is not "
                                 f"supported; the harness runs {fixed[key]!r}")
    return cfg


def spec(data: dict) -> SimSpec:
    f32 = jnp.float32
    params = STOParams(**{k: jnp.asarray(v, f32) for k, v in data["params"].items()})
    return SimSpec(
        params=params,
        w_cp=jnp.asarray(data["w"], f32),
        w_in=jnp.asarray(data["w_in"], f32),
        m0=jnp.asarray(data["m0"], f32),
        dt=data["dt"],
        hold_steps=data["hold_steps"],
        tableau=SPEC_FIXED["tableau"],
    )


def engine(cfg: dict, data: dict, interpret: bool = False):
    """compile_plan(spec, ExecPlan(...)) and the engine over it."""
    plan = validate(cfg)["plan"]
    sim = compile_plan(spec(data), ExecPlan(
        impl=plan["impl"], ensemble=int(plan["ensemble"]),
        chunk_ticks=int(plan["chunk_ticks"]), precision=plan["precision"],
        interpret=interpret,
    ))
    return ReservoirEngine(sim, n_out=int(cfg["readout"]["n_out"]))


def impl(eng) -> str:
    return eng.backend


def session(sid: int, u: np.ndarray, readout: np.ndarray):
    return StreamSession(sid=sid, u_seq=u, readout=Readout(w_out=readout, washout=0),
                         collect_states=False)
