"""The system under test, as the benchmark sees it.

Everything the benchmark takes from the program goes through here: the
deployment is handed to `compile_plan` as a `SimSpec` built from the
benchmark's own data, and the engine is driven through its public surface
only (`submit`, `step_chunk`, `pop_results`, and after the window
`snapshot_sessions`).
"""

from __future__ import annotations

import math
import os
import sys
from typing import Optional

from benchlib.registry import ROOT

if os.path.join(ROOT, "src") not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

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
PLAN_KEYS = {"impl", "ensemble", "chunk_ticks", "precision",
             "learn", "learn_lam", "learn_reg", "mesh"}
PLAN_FIXED = {"autoscale": None}
# learn: null (inference only) or "rls", which has to state both of these
LEARNERS = (None, "rls")
LEARN_KNOBS = ("learn_lam", "learn_reg")
# mesh: an ordered map of axis sizes; lanes (E) span "data", N spans "model"
MESH_AXES = ("data", "model")


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
    plan = cfg["plan"]
    learn = plan.get("learn")
    if learn not in LEARNERS:
        raise ValueError(f"configuration plan.learn={learn!r} is not supported; "
                         f"the harness runs one of {LEARNERS}")
    for key in LEARN_KNOBS:
        stated = plan.get(key) is not None
        if learn is not None and not stated:
            raise ValueError(f"configuration plan.learn={learn!r} states no "
                             f"plan.{key}")
        if learn is None and stated:
            raise ValueError(f"configuration plan.{key} is set but plan.learn is null")
    mesh = plan.get("mesh")
    if mesh is not None:
        if (not isinstance(mesh, dict) or "data" not in mesh
                or any(a not in MESH_AXES for a in mesh)
                or any(not isinstance(v, int) or isinstance(v, bool) or v < 1
                       for v in mesh.values())):
            raise ValueError(f"configuration plan.mesh={mesh!r} must map 'data' "
                             f"(and optionally 'model') to whole sizes >= 1")
    return cfg


def validate_deployment(cfg: dict, mix: dict, chips: int) -> None:
    """Refuse, before any run, a deployment that the cell cannot run as
    stated: a mesh that is not the cell's chips, lanes that do not divide
    over the data axis, learning without targets or targets without it."""
    plan = validate(cfg)["plan"]
    sizes = plan.get("mesh") or {}
    if math.prod(sizes.values()) != chips:
        raise ValueError(f"configuration plan.mesh={plan.get('mesh')!r} spans "
                         f"{math.prod(sizes.values())} chips; the cell asks for {chips}")
    if int(plan["ensemble"]) % sizes.get("data", 1):
        raise ValueError(f"configuration plan.ensemble={plan['ensemble']} does not "
                         f"divide over plan.mesh data={sizes['data']}")
    learning = plan.get("learn") is not None
    if learning and "targets" not in mix:
        raise ValueError(f"configuration plan.learn={plan['learn']!r} needs a "
                         "traffic mix with targets")
    if "targets" in mix and not learning:
        raise ValueError(f"traffic targets={mix['targets']!r} need a learning "
                         "configuration (plan.learn is null)")
    if "targets" in mix and int(cfg["spec"]["n_in"]) != 1:
        raise ValueError(f"traffic targets={mix['targets']!r} take one input "
                         f"(spec.n_in is {cfg['spec']['n_in']})")


def mesh(cfg: dict, chips: int) -> Optional[Mesh]:
    """The cell's mesh over its first `chips` devices, or None."""
    sizes = cfg["plan"].get("mesh")
    if sizes is None:
        return None
    devices = np.asarray(jax.local_devices()[:chips])
    return Mesh(devices.reshape(tuple(sizes.values())), tuple(sizes))


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


def engine(cfg: dict, data: dict, chips: int = 1, interpret: bool = False):
    """compile_plan(spec, ExecPlan(...)) and the engine over it."""
    plan = validate(cfg)["plan"]
    extra = {}
    if plan.get("learn") is not None:
        extra.update(learn=plan["learn"], learn_lam=float(plan["learn_lam"]),
                     learn_reg=float(plan["learn_reg"]))
    grid = mesh(cfg, chips)
    if grid is not None:
        extra.update(mesh=grid, ensemble_axes=("data",),
                     model_axis="model" if "model" in grid.axis_names else None)
    sim = compile_plan(spec(data), ExecPlan(
        impl=plan["impl"], ensemble=int(plan["ensemble"]),
        chunk_ticks=int(plan["chunk_ticks"]), precision=plan["precision"],
        interpret=interpret, **extra,
    ))
    return ReservoirEngine(sim, n_out=int(cfg["readout"]["n_out"]))


def impl(eng) -> str:
    return eng.backend


def session(sid: int, u: np.ndarray, readout: Optional[np.ndarray],
            targets: Optional[np.ndarray] = None, washout: int = 0):
    """A readout session, or with targets a session that learns its readout
    from zero weights (no update in its first `washout` ticks)."""
    if targets is None:
        return StreamSession(sid=sid, u_seq=u, readout=Readout(w_out=readout, washout=0),
                             collect_states=False)
    return StreamSession(sid=sid, u_seq=u, targets=targets, learn_washout=washout,
                         collect_states=False)
