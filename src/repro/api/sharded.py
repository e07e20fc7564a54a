"""Sharded execution bodies for ExecPlan(mesh=...) plans.

The shard_map decomposition (formerly core/ensemble.py, now owned by the
unified API): the ensemble axis E spans the data/pod mesh axes and the
oscillator axis N spans the model axis. W^cp is row-sharded and each RK
stage all-gathers the m^x slice (N*E_local floats — negligible next to the
O(N^2 E) compute). PartitionSpecs come from
`distributed.sharding.reservoir_specs` so every sharded reservoir path in
the repo agrees on the layout.

`gather_dtype` (e.g. jnp.bfloat16) runs the COUPLING PATH in reduced
precision: m^x is cast before the all-gather (half the wire bytes) and the
coupling matmul runs bf16 x bf16 -> f32 (MXU-native accumulate). Consuming
bf16 directly in the dot is what keeps XLA from cancelling the converts
around the collective and silently restoring an f32 gather (observed;
§Perf C). Physically benign: |H_cp| <= A_cp ~ 1 Oe against ~600 Oe local
fields, and |m|=1 conservation is structural.

`ExecPlan.precision` subsumes that ad-hoc knob: "bf16_coupling"/"mixed"
plans resolve to gather_dtype=bf16 (an explicit gather_dtype still wins —
see ExecPlan.effective_gather_dtype), and "mixed" additionally runs the
input-field GEMM (W^in u) on bf16 operands (`_input_field_local`). The
`precision` argument on every body here is part of the lru_cache key, so
plans of different precision never share a trace.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.core.compat import SHARD_MAP_CHECK_KW as _SHARD_MAP_CHECK_KW
from repro.core.compat import shard_map
from repro.core import integrators, sto
from repro.core.constants import EXACT_MATMUL, STOParams
from repro.distributed.sharding import auto_axes, reservoir_specs
from repro.kernels import rls as krls


def _input_field_local(params_l, win_l, u_t, precision, per_lane=True):
    """h_in = A_in * (W^in_local u_t), honoring the precision policy.

    The reduction policy itself lives in `kernels.ops.input_field_einsum`
    (shared with the planes workers); this wrapper owns the sharded
    layouts and the legacy a_in op order.
    """
    from repro.kernels import ops as kops

    eq = "ni,ei->en" if per_lane else "ni,i->n"
    return params_l.a_in * kops.input_field_einsum(eq, win_l, u_t, precision)


def _coupling_field(params_l, w_mm, m, model_axis, gather_dtype):
    """h_x = A_cp * W^cp_local @ all-gather(m^x): the one collective per stage."""
    mx = m[..., 0]  # (E_l, N_l)
    if gather_dtype is not None:
        mx = mx.astype(gather_dtype)
    if model_axis is not None:
        mx_full = jax.lax.all_gather(mx, model_axis, axis=-1, tiled=True)
    else:
        mx_full = mx
    return params_l.a_cp * jnp.einsum(
        "ki,...i->...k", w_mm, mx_full, preferred_element_type=m.dtype,
        precision=EXACT_MATMUL,
    )


def integrate_sharded(
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
    precision=None,  # free-run has no input GEMM; coupling rides gather_dtype
):
    """Free-running (u = 0) sharded ensemble integration -> final (E, N, 3)."""
    mesh = auto_axes(mesh)
    tableau = integrators.TABLEAUX[tableau_name]
    specs = reservoir_specs(ensemble_axes, model_axis)

    def local_run(params_l: STOParams, w_l, m0_l):
        w_mm = w_l.astype(gather_dtype) if gather_dtype is not None else w_l

        def field(m, _):
            h_x = _coupling_field(params_l, w_mm, m, model_axis, gather_dtype)
            b = sto.effective_field_b(m, params_l, h_x)
            return sto.llg_rhs_from_b(m, b, params_l)

        yT, _ = integrators.integrate_scan(field, m0_l, dt, n_steps, None, tableau)
        return yT

    fn = shard_map(
        local_run,
        mesh=mesh,
        in_specs=(
            jax.tree.map(lambda _: specs["params"], params),
            specs["w"],
            specs["m"],
        ),
        out_specs=specs["m"],
        **_SHARD_MAP_CHECK_KW,
    )
    return fn(params, w_cp, m0)


def drive_sharded(
    mesh: Mesh,
    params: STOParams,  # leaves (E, 1)
    w_cp: jnp.ndarray,  # (N, N)
    w_in: jnp.ndarray,  # (N, N_in)
    m0: jnp.ndarray,  # (E, N, 3)
    u_seq: jnp.ndarray,  # (T, N_in) shared series OR (T, E, N_in) per lane
    dt: float,
    hold_steps: int,
    ensemble_axes: Sequence[str] = ("data",),
    model_axis: Optional[str] = "model",
    tableau_name: str = "rk4",
    gather_dtype=None,
    precision=None,
):
    """Reservoir DRIVE (input on) for a sharded ensemble.

    Returns (mT (E, N, 3), states (T, E, N)) with states = m^x sampled after
    each hold window — the full paper application (sweep + drive + readout)
    on the production mesh. The input field h_in = A_in * (W_in u_t) depends
    only on the LOCAL N rows, so the input path adds no collectives; only
    the coupling gathers.
    """
    mesh = auto_axes(mesh)
    tableau = integrators.TABLEAUX[tableau_name]
    specs = reservoir_specs(ensemble_axes, model_axis)
    per_lane_u = u_seq.ndim == 3

    def local_run(params_l: STOParams, w_l, win_l, m0_l, u):
        w_mm = w_l.astype(gather_dtype) if gather_dtype is not None else w_l

        def field(m, h_in_x):
            h_x = _coupling_field(params_l, w_mm, m, model_axis, gather_dtype)
            h_x = h_x + h_in_x
            b = sto.effective_field_b(m, params_l, h_x)
            return sto.llg_rhs_from_b(m, b, params_l)

        step = integrators.make_step(field, tableau)
        dt_c = jnp.asarray(dt, m0_l.dtype)

        def per_sample(m, u_t):
            h_in = _input_field_local(params_l, win_l, u_t, precision, per_lane_u)
            h_in = jnp.broadcast_to(h_in, m[..., 0].shape)

            def inner(mi, _):
                return step(mi, dt_c, h_in), None

            m, _ = jax.lax.scan(inner, m, None, length=hold_steps)
            return m, m[..., 0]

        mT, states = jax.lax.scan(per_sample, m0_l, u)
        return mT, states

    fn = shard_map(
        local_run,
        mesh=mesh,
        in_specs=(
            jax.tree.map(lambda _: specs["params"], params),
            specs["w"],
            specs["w_in"],
            specs["m"],
            specs["u_e"] if per_lane_u else specs["u"],
        ),
        out_specs=(specs["m"], specs["states"]),
        **_SHARD_MAP_CHECK_KW,
    )
    return fn(params, w_cp, w_in, m0, u_seq)


@functools.lru_cache(maxsize=None)
def _tick_sharded_fn(
    mesh: Mesh,
    ensemble_axes: tuple,
    model_axis: Optional[str],
    tableau_name: str,
    dt: float,
    hold_steps: int,
    gather_dtype,
    precision=None,
):
    """Build (once per signature) the jit'd shard_map'd tick.

    The serving engine calls the tick every input sample — a fresh shard_map
    closure per call would defeat JAX's compilation cache and retrace the
    whole hold-window scan each tick, so the wrapped callable is cached on
    everything that shapes the trace (mesh/axes/tableau/dt/hold/gather).
    """
    tableau = integrators.TABLEAUX[tableau_name]
    specs = reservoir_specs(ensemble_axes, model_axis)

    def local_run(params_l: STOParams, w_l, win_l, m_l, u_l, mask_l):
        w_mm = w_l.astype(gather_dtype) if gather_dtype is not None else w_l

        def field(mm, h_in_x):
            h_x = _coupling_field(params_l, w_mm, mm, model_axis, gather_dtype)
            h_x = h_x + h_in_x
            b = sto.effective_field_b(mm, params_l, h_x)
            return sto.llg_rhs_from_b(mm, b, params_l)

        step = integrators.make_step(field, tableau)
        dt_c = jnp.asarray(dt, m_l.dtype)
        h_in = _input_field_local(params_l, win_l, u_l, precision)  # (E_l, N_l)

        def inner(mi, _):
            return step(mi, dt_c, h_in), None

        m_new, _ = jax.lax.scan(inner, m_l, None, length=hold_steps)
        m_new = jnp.where(mask_l[:, None, None], m_new, m_l)
        return m_new, m_new[..., 0]

    p_params = STOParams(*([specs["params"]] * len(STOParams._fields)))
    return jax.jit(
        shard_map(
            local_run,
            mesh=mesh,
            in_specs=(
                p_params,
                specs["w"],
                specs["w_in"],
                specs["m"],
                specs["u_tick"],
                specs["lane"],
            ),
            out_specs=(specs["m"], specs["states_tick"]),
            **_SHARD_MAP_CHECK_KW,
        )
    )


@functools.lru_cache(maxsize=None)
def _tick_chunk_sharded_fn(
    mesh: Mesh,
    ensemble_axes: tuple,
    model_axis: Optional[str],
    tableau_name: str,
    dt: float,
    hold_steps: int,
    gather_dtype,
    precision=None,
):
    """Build (once per signature) the jit'd shard_map'd K-tick chunk.

    Chunked serving's sharded path: the local body scans over the K input
    ticks, so per-tick states stay device-side and shard-local until the
    engine's once-per-chunk harvest. Cached like `_tick_sharded_fn` — the
    engine calls this every chunk and a fresh closure would retrace.
    """
    tableau = integrators.TABLEAUX[tableau_name]
    specs = reservoir_specs(ensemble_axes, model_axis)

    def local_run(params_l: STOParams, w_l, win_l, m_l, u_l, mask_l):
        # u_l: (K, E_l, N_in), mask_l: (K, E_l)
        w_mm = w_l.astype(gather_dtype) if gather_dtype is not None else w_l

        def field(mm, h_in_x):
            h_x = _coupling_field(params_l, w_mm, mm, model_axis, gather_dtype)
            h_x = h_x + h_in_x
            b = sto.effective_field_b(mm, params_l, h_x)
            return sto.llg_rhs_from_b(mm, b, params_l)

        step = integrators.make_step(field, tableau)
        dt_c = jnp.asarray(dt, m_l.dtype)

        def per_tick(m_c, tick_in):
            u_t, mask_t = tick_in
            h_in = _input_field_local(params_l, win_l, u_t, precision)

            def inner(mi, _):
                return step(mi, dt_c, h_in), None

            m_new, _ = jax.lax.scan(inner, m_c, None, length=hold_steps)
            m_new = jnp.where(mask_t[:, None, None], m_new, m_c)
            return m_new, m_new[..., 0]

        mT, states = jax.lax.scan(per_tick, m_l, (u_l, mask_l))
        return mT, states  # (E_l, N_l, 3), (K, E_l, N_l)

    p_params = STOParams(*([specs["params"]] * len(STOParams._fields)))
    return jax.jit(
        shard_map(
            local_run,
            mesh=mesh,
            in_specs=(
                p_params,
                specs["w"],
                specs["w_in"],
                specs["m"],
                specs["u_e"],
                specs["lane_block"],
            ),
            out_specs=(specs["m"], specs["states"]),
            **_SHARD_MAP_CHECK_KW,
        )
    )


@functools.lru_cache(maxsize=None)
def _tick_chunk_sharded_rls_fn(
    mesh: Mesh,
    ensemble_axes: tuple,
    model_axis: Optional[str],
    tableau_name: str,
    dt: float,
    hold_steps: int,
    gather_dtype,
    lam: float,  # static: the RLS update specializes on it (kernels/rls.py)
    precision=None,
):
    """Build (once per signature) the jit'd shard_map'd learning K-chunk.

    `_tick_chunk_sharded_fn` + the chunked RLS readout update
    (ExecPlan.learn="rls"). P and W ride LANE-sharded — the ensemble axes
    split E, the (S, S) feature block is replicated — while the feature
    block (the full N node states + bias) is all-gathered over the model
    axis ONCE per chunk, like the coupling field's m^x but K ticks at a
    time; `kernels.rls.rls_chunk` then runs shard-locally on the lane
    shard.
    """
    tableau = integrators.TABLEAUX[tableau_name]
    specs = reservoir_specs(ensemble_axes, model_axis)

    def local_run(params_l: STOParams, w_l, win_l, m_l, u_l, mask_l,
                  y_l, lmask_l, p_l, wl_l):
        # u_l (K, E_l, N_in), mask_l/lmask_l (K, E_l), y_l (K, E_l, n_out),
        # p_l (E_l, S, S), wl_l (E_l, S, n_out)
        w_mm = w_l.astype(gather_dtype) if gather_dtype is not None else w_l

        def field(mm, h_in_x):
            h_x = _coupling_field(params_l, w_mm, mm, model_axis, gather_dtype)
            h_x = h_x + h_in_x
            b = sto.effective_field_b(mm, params_l, h_x)
            return sto.llg_rhs_from_b(mm, b, params_l)

        step = integrators.make_step(field, tableau)
        dt_c = jnp.asarray(dt, m_l.dtype)

        def per_tick(m_c, tick_in):
            u_t, mask_t = tick_in
            h_in = _input_field_local(params_l, win_l, u_t, precision)

            def inner(mi, _):
                return step(mi, dt_c, h_in), None

            m_new, _ = jax.lax.scan(inner, m_c, None, length=hold_steps)
            m_new = jnp.where(mask_t[:, None, None], m_new, m_c)
            return m_new, m_new[..., 0]

        mT, states = jax.lax.scan(per_tick, m_l, (u_l, mask_l))
        # full-N feature block for the lane-sharded learn state: one gather
        # per chunk over the model axis (K, E_l, N_l) -> (K, E_l, N)
        sx = states
        if model_axis is not None:
            sx = jax.lax.all_gather(sx, model_axis, axis=-1, tiled=True)
        xb = jnp.concatenate(
            [sx, jnp.ones((*sx.shape[:2], 1), sx.dtype)], axis=-1
        )
        pT, wT, preds = krls.rls_chunk(p_l, wl_l, xb, y_l, lmask_l, lam)
        return mT, states, pT, wT, preds

    p_params = STOParams(*([specs["params"]] * len(STOParams._fields)))
    return jax.jit(
        shard_map(
            local_run,
            mesh=mesh,
            in_specs=(
                p_params,
                specs["w"],
                specs["w_in"],
                specs["m"],
                specs["u_e"],
                specs["lane_block"],
                specs["y_block"],
                specs["lane_block"],
                specs["learn_p"],
                specs["learn_w"],
            ),
            out_specs=(
                specs["m"],
                specs["states"],
                specs["learn_p"],
                specs["learn_w"],
                specs["y_block"],
            ),
            **_SHARD_MAP_CHECK_KW,
        )
    )


def tick_chunk_sharded_rls(
    mesh: Mesh,
    params: STOParams,  # leaves (E, 1)
    w_cp: jnp.ndarray,  # (N, N)
    w_in: jnp.ndarray,  # (N, N_in)
    m: jnp.ndarray,  # (E, N, 3)
    u_block: jnp.ndarray,  # (K, E, N_in)
    mask_block: jnp.ndarray,  # (K, E) bool — integration lane mask
    y_block: jnp.ndarray,  # (K, E, n_out) per-tick learning targets
    lmask_block: jnp.ndarray,  # (K, E) bool — which lanes LEARN which ticks
    p0: jnp.ndarray,  # (E, S, S) per-lane RLS inverse-Gram
    w0: jnp.ndarray,  # (E, S, n_out) per-lane readout weights
    lam: float,  # forgetting factor (static)
    dt: float,
    hold_steps: int,
    ensemble_axes: Sequence[str] = ("data",),
    model_axis: Optional[str] = "model",
    tableau_name: str = "rk4",
    gather_dtype=None,
    precision=None,
):
    """K learning serving ticks for a sharded slot batch in one dispatch.

    The sharded analogue of the learn branch of `CompiledSim.tick_chunk`:
    integration is `tick_chunk_sharded`'s exactly; the fused RLS update
    keeps P/W lane-sharded and all-gathers the feature vector over the
    model axis. Returns (m' (E, N, 3), states (K, E, N), P', W',
    preds (K, E, n_out)).
    """
    mesh = auto_axes(mesh)
    fn = _tick_chunk_sharded_rls_fn(
        mesh, tuple(ensemble_axes), model_axis, tableau_name,
        float(dt), int(hold_steps), gather_dtype, float(lam), precision,
    )
    return fn(params, w_cp, w_in, m, u_block, mask_block,
              y_block, lmask_block, p0, w0)


def tick_chunk_sharded(
    mesh: Mesh,
    params: STOParams,  # leaves (E, 1)
    w_cp: jnp.ndarray,  # (N, N)
    w_in: jnp.ndarray,  # (N, N_in)
    m: jnp.ndarray,  # (E, N, 3)
    u_block: jnp.ndarray,  # (K, E, N_in) input rows for K ticks
    mask_block: jnp.ndarray,  # (K, E) bool; False = lane frozen that tick
    dt: float,
    hold_steps: int,
    ensemble_axes: Sequence[str] = ("data",),
    model_axis: Optional[str] = "model",
    tableau_name: str = "rk4",
    gather_dtype=None,
    precision=None,
):
    """K serving ticks for a sharded slot batch in one dispatch.

    The sharded analogue of `CompiledSim.tick_chunk`: per-tick lane masks
    support mid-chunk admit/retire (masked ticks are bit-identical), and the
    (K, E, N) states block stays on device until the engine's bulk harvest.
    Returns (m' (E, N, 3), states (K, E, N)).
    """
    mesh = auto_axes(mesh)
    fn = _tick_chunk_sharded_fn(
        mesh, tuple(ensemble_axes), model_axis, tableau_name,
        float(dt), int(hold_steps), gather_dtype, precision,
    )
    return fn(params, w_cp, w_in, m, u_block, mask_block)


def tick_sharded(
    mesh: Mesh,
    params: STOParams,  # leaves (E, 1)
    w_cp: jnp.ndarray,  # (N, N)
    w_in: jnp.ndarray,  # (N, N_in)
    m: jnp.ndarray,  # (E, N, 3)
    u: jnp.ndarray,  # (E, N_in) — this tick's input row per lane
    lane_mask: jnp.ndarray,  # (E,) bool; False lanes return unchanged
    dt: float,
    hold_steps: int,
    ensemble_axes: Sequence[str] = ("data",),
    model_axis: Optional[str] = "model",
    tableau_name: str = "rk4",
    gather_dtype=None,
    precision=None,
):
    """One serving tick (a full hold window) for a sharded slot batch.

    The sharded analogue of the engine's batched tick: per-tenant params ride
    in the (E, 1) leaves, the input row is per lane, and masked lanes come
    back bit-identical so idle serving slots stay frozen. Returns
    (m' (E, N, 3), states (E, N)).
    """
    mesh = auto_axes(mesh)
    fn = _tick_sharded_fn(
        mesh, tuple(ensemble_axes), model_axis, tableau_name,
        float(dt), int(hold_steps), gather_dtype, precision,
    )
    return fn(params, w_cp, w_in, m, u, lane_mask)
