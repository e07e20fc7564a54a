"""Ensemble (batched) and sharded execution — legacy shims + param helpers.

The execution bodies moved into the unified API (`repro.api`): ensemble
width, impl dispatch, and mesh sharding are ExecPlan decisions resolved by
`repro.api.compile_plan`, and the shard_map decompositions live in
`repro.api.sharded` (PartitionSpecs from
`distributed.sharding.reservoir_specs`). What remains here:

- `broadcast_params` builds an ensemble of parameter sets (the paper's
  motivating use-case: sweeping physical parameters is "a computationally
  expensive task", §2) — pure pytree plumbing, still first-class.
- `fit_ridge_ensemble` per-member ridge readouts.
- `integrate_ensemble` / `integrate_ensemble_sharded`: thin DEPRECATED
  shims over compile_plan, kept signature-compatible (and, for the
  unsharded path, bit-identical — the api's impl="scan" runs the same op
  sequence).
- `drive_ensemble_sharded`: delegates to `repro.api.sharded.drive_sharded`
  (prefer `compile_plan(spec, ExecPlan(mesh=...)).drive_batch(u)`).
"""

from __future__ import annotations

import warnings
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding

from repro.core.constants import EXACT_MATMUL, STOParams


def broadcast_params(base: STOParams, size: int, **sweeps) -> STOParams:
    """Ensemble of parameter sets with leaves shaped (E, 1).

    The (E, 1) trailing singleton broadcasts against per-oscillator arrays of
    shape (E, N). Any keyword in `sweeps` supplies a length-E array for that
    field; all other fields are tiled from `base`.
    """
    leaves = {}
    for name in base._fields:
        if name in sweeps:
            v = jnp.asarray(sweeps[name], dtype=base.gamma.dtype).reshape(size, 1)
        else:
            v = jnp.broadcast_to(getattr(base, name), (size, 1))
        leaves[name] = v
    unknown = set(sweeps) - set(base._fields)
    if unknown:
        raise ValueError(f"unknown sweep fields: {sorted(unknown)}")
    return STOParams(**leaves)


def _spec_for(params: STOParams, w_cp, m0, dt, hold_steps, tableau_name):
    """Wrap legacy ensemble arguments in a SimSpec (no input topology)."""
    from repro import api

    n = int(m0.shape[-2])
    return api.SimSpec(
        params=params,
        w_cp=w_cp,
        w_in=jnp.zeros((n, 1), dtype=m0.dtype),
        m0=m0[0] if m0.ndim == 3 else m0,
        dt=dt,
        hold_steps=hold_steps,
        tableau=tableau_name,
    )


def integrate_ensemble(
    params: STOParams,  # leaves (E, 1)
    w_cp: jnp.ndarray,  # (N, N), shared topology
    m0: jnp.ndarray,  # (E, N, 3)
    dt: float,
    n_steps: int,
    tableau_name: str = "rk4",
    save_every: int = 0,
):
    """Batched integration of E independent reservoirs (shared W^cp).

    .. deprecated:: thin shim over `repro.api.compile_plan(spec,
       ensemble=E, impl="scan").integrate(n_steps, ...)` — bit-identical.
    """
    warnings.warn(
        "repro.core.ensemble.integrate_ensemble is deprecated; use "
        "repro.api.compile_plan(spec, ensemble=E).integrate(n_steps, ...)",
        DeprecationWarning,
        stacklevel=2,
    )
    from repro import api

    sim = api.compile_plan(
        _spec_for(params, w_cp, m0, dt, 1, tableau_name),
        impl="scan",
        ensemble=int(m0.shape[0]),
    )
    return sim.integrate(n_steps, m0=m0, save_every=save_every, params=params)


def integrate_ensemble_sharded(
    mesh: Mesh,
    params: STOParams,  # leaves (E, 1)
    w_cp: jnp.ndarray,  # (N, N)
    m0: jnp.ndarray,  # (E, N, 3)
    dt: float,
    n_steps: int,
    ensemble_axes: Sequence[str] = ("data",),
    model_axis: Optional[str] = "model",
    tableau_name: str = "rk4",
    gather_dtype=None,
):
    """shard_map'd integration: E over `ensemble_axes`, N over `model_axis`.

    .. deprecated:: thin shim over `repro.api.compile_plan(spec,
       ExecPlan(mesh=...)).integrate(n_steps)`; the shard_map body now lives
       in `repro.api.sharded.integrate_sharded` (same decomposition, same
       gather_dtype semantics — see that module's docstring).
    """
    warnings.warn(
        "repro.core.ensemble.integrate_ensemble_sharded is deprecated; use "
        "repro.api.compile_plan(spec, ExecPlan(mesh=...)).integrate(n_steps)",
        DeprecationWarning,
        stacklevel=2,
    )
    from repro import api

    sim = api.compile_plan(
        _spec_for(params, w_cp, m0, dt, 1, tableau_name),
        api.ExecPlan(
            ensemble=int(m0.shape[0]),
            mesh=mesh,
            ensemble_axes=tuple(ensemble_axes),
            model_axis=model_axis,
            gather_dtype=gather_dtype,
        ),
    )
    mT, _ = sim.integrate(n_steps, m0=m0, params=params)
    return mT


def drive_ensemble_sharded(
    mesh: Mesh,
    params: STOParams,  # leaves (E, 1)
    w_cp: jnp.ndarray,  # (N, N)
    w_in: jnp.ndarray,  # (N, N_in)
    m0: jnp.ndarray,  # (E, N, 3)
    u_seq: jnp.ndarray,  # (T, N_in) — shared input series
    dt: float,
    hold_steps: int,
    ensemble_axes: Sequence[str] = ("data",),
    model_axis: Optional[str] = "model",
    tableau_name: str = "rk4",
    gather_dtype=None,
):
    """Reservoir DRIVE (input on) for a sharded ensemble. Returns
    (mT (E,N,3), states (T,E,N)). Delegates to the unified API's sharded
    body; prefer `compile_plan(spec, ExecPlan(mesh=...)).drive_batch(u)`.
    """
    from repro.api import sharded

    return sharded.drive_sharded(
        mesh, params, w_cp, w_in, m0, u_seq, dt, hold_steps,
        ensemble_axes=ensemble_axes, model_axis=model_axis,
        tableau_name=tableau_name, gather_dtype=gather_dtype,
    )


def fit_ridge_ensemble(states: jnp.ndarray, targets: jnp.ndarray, reg: float = 1e-6,
                       washout: int = 0):
    """Per-member ridge readouts. states: (T, E, N); targets: (T, n_out)
    (shared targets across the sweep). Returns w_out (E, N+1, n_out).

    The (N+1)^2 solves are tiny next to the drive; a vmap suffices even at
    production sizes (the gram matrices live per member)."""
    x = states[washout:]
    y = targets[washout:]

    def fit_one(xe):  # (T', N)
        ones = jnp.ones((xe.shape[0], 1), xe.dtype)
        xb = jnp.concatenate([xe, ones], axis=1)
        gram = jnp.matmul(xb.T, xb, precision=EXACT_MATMUL)
        rhs = jnp.matmul(xb.T, y.astype(xe.dtype), precision=EXACT_MATMUL)
        return jnp.linalg.solve(
            gram + reg * jnp.eye(gram.shape[0], dtype=gram.dtype), rhs
        )

    return jax.vmap(fit_one, in_axes=1)(x)


def lower_sharded_ensemble(
    mesh: Mesh,
    n: int,
    e: int,
    dt: float,
    n_steps: int,
    ensemble_axes: Sequence[str] = ("data",),
    model_axis: Optional[str] = "model",
    dtype=jnp.bfloat16,
    gather_dtype=None,
):
    """Dry-run entry: lower+compile the sharded ensemble integrator from
    ShapeDtypeStructs (no allocation). Returns the jax `Lowered`."""
    from repro.api import sharded
    from repro.core import constants
    from repro.distributed.sharding import reservoir_specs

    base = constants.default_params(dtype)
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((e, 1), x.dtype),
        broadcast_params(base, 1),
    )
    w = jax.ShapeDtypeStruct((n, n), dtype)
    m0 = jax.ShapeDtypeStruct((e, n, 3), dtype)

    specs = reservoir_specs(tuple(ensemble_axes), model_axis)
    shardings = (
        jax.tree.map(lambda _: NamedSharding(mesh, specs["params"]), params),
        NamedSharding(mesh, specs["w"]),
        NamedSharding(mesh, specs["m"]),
    )

    def run(params_, w_, m0_):
        return sharded.integrate_sharded(
            mesh, params_, w_, m0_, dt, n_steps,
            ensemble_axes=ensemble_axes, model_axis=model_axis,
            gather_dtype=gather_dtype,
        )

    return jax.jit(run, in_shardings=shardings).lower(params, w, m0)
