"""Pallas TPU kernels for the coupled-STO RK4 step.

Three kernels, specialized by regime — mirroring the paper's finding that
each implementation wins in a different range (Table 2):

1. `rk4_fused`  (small/medium N): the ENTIRE RK4 step — all four field
   evaluations, the coupling matmuls, and the combine — plus `n_inner`
   consecutive time steps run inside one kernel invocation. W^cp, the state
   and all stage slopes stay VMEM-resident; HBM sees one state read + one
   state write (+ one W read) per n_inner steps. Grid tiles only the
   ensemble axis E. This is the TPU answer to the paper's observation that
   per-step dispatch dominates at small N.

2. `field_tiled` (large N): one field evaluation, tiled over (N-rows, E).
   Each row tile contracts its W^cp row block against the full m^x plane
   (the O(N^2) coupling) on the MXU and fuses all elementwise LLG terms in
   the same kernel. The RK4 driver in ops.py calls it four times per step;
   stage algebra y = m + c*k is fused into the kernel (classic RK4 has a
   single-predecessor tableau), so HBM traffic per stage is W-row-tile +
   3 state planes instead of ~13 op-by-op round trips.

3. `rk4_chunk` (chunked serving): the ENTIRE K-tick serving chunk — K
   input ticks x hold_steps x 4 RK4 stages — in one kernel invocation.
   Where `rk4_fused` is re-launched per tick (re-reading W from HBM each
   launch), `rk4_chunk` keeps W and the state planes VMEM-resident across
   the whole chunk: HBM sees one W read + one state read/write + the
   (K, N, be) input and states blocks per chunk per ensemble tile. Per-tick
   lane masks ride in as an f32 0/1 plane so mid-chunk admit/retire works
   inside the kernel.

Reduced-precision coupling (ExecPlan.precision): every kernel accepts a W
operand whose dtype differs from the state's (cast ONCE by ops.py, not per
stage); the coupling dot then consumes reduced operands (bf16 x bf16 ->
f32 is MXU-native) while all elementwise math and the state carry stay in
the state dtype.

Layouts (see kernels/ref.py): m (3, N, E); W (N, N); params (NP, E).
MXU alignment: E and N tiles are multiples of 128 (f32); callers pad via
ops.py (zero-padding is algebraically inert for both N and E axes: padded
W rows/cols are zero and padded lanes are dropped on unpad).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.constants import EXACT_MATMUL
from repro.kernels.ref import NP

# MXU/VREG-aligned tile sizes (f32).
LANE = 128
SUBLANE = 8

# Scoped-VMEM limit every pallas_call here compiles under. ops.py's fit
# checks size the kernels against this same number.
VMEM_LIMIT_BYTES = 16 * 1024 * 1024


def _coupling_dot(w, x, acc_t):
    """W @ x on the MXU, accumulating in acc_t.

    f32 operands ask for EXACT_MATMUL (see core/constants.py). Reduced-
    precision operands (bf16 W) are one bf16 pass by construction, and
    Mosaic refuses HIGHEST on them.
    """
    precision = EXACT_MATMUL if w.dtype == jnp.float32 else None
    return jnp.dot(w, x, preferred_element_type=acc_t, precision=precision)


def _compiler_params():
    return pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _field_planes(mx, my, mz, hx, p):
    """Elementwise LLG slope given the coupling/input x-field hx.

    All inputs (bn, be); p is a dict of (1, be) parameter rows. Returns
    (kx, ky, kz). Pure VPU work; the MXU part (hx) is computed by callers.
    """
    hz = p["happl"] + p["demag"] * mz
    mdotp = p["px"] * mx + p["py"] * my + p["pz"] * mz
    hs = p["hs_coef"] / (1.0 + p["lam"] * mdotp)
    bx = hx + hs * (p["py"] * mz - p["pz"] * my)
    by = hs * (p["pz"] * mx - p["px"] * mz)
    bz = hz + hs * (p["px"] * my - p["py"] * mx)
    cx = my * bz - mz * by
    cy = mz * bx - mx * bz
    cz = mx * by - my * bx
    dx = my * cz - mz * cy
    dy = mz * cx - mx * cz
    dz = mx * cy - my * cx
    napref = -p["pref"]
    al = p["alpha"]
    kx = napref * (cx + al * dx)
    ky = napref * (cy + al * dy)
    kz = napref * (cz + al * dz)
    return kx, ky, kz


def _unpack_rows(params_ref):
    from repro.kernels.ref import PARAM_LAYOUT

    return {name: params_ref[i : i + 1, :] for i, name in enumerate(PARAM_LAYOUT)}


# ---------------------------------------------------------------------------
# Kernel 1: fully fused RK4 (+ multi-step), W and state VMEM-resident
# ---------------------------------------------------------------------------


def _rk4_fused_kernel(params_ref, w_ref, h_ref, m_ref, out_ref, *, dt, n_inner):
    p = _unpack_rows(params_ref)
    w = w_ref[...]  # (N, N) stays in VMEM across inner steps
    h_in = h_ref[...]  # (N, be) input-drive x-field, constant over the window
    acc_t = jnp.float32 if m_ref.dtype == jnp.bfloat16 else m_ref.dtype

    def field(mx, my, mz):
        # reduced-precision coupling (ExecPlan.precision): callers pass W
        # pre-cast (e.g. bf16); the dot consumes the reduced operands and
        # accumulates in the state dtype (MXU-native bf16 x bf16 -> f32)
        mx_cp = mx if w.dtype == m_ref.dtype else mx.astype(w.dtype)
        hx = p["a_cp"] * _coupling_dot(w, mx_cp, acc_t) + h_in
        return _field_planes(mx, my, mz, hx, p)

    def one_step(state):
        mx, my, mz = state
        h = dt / 2.0
        k1x, k1y, k1z = field(mx, my, mz)
        k2x, k2y, k2z = field(mx + h * k1x, my + h * k1y, mz + h * k1z)
        k3x, k3y, k3z = field(mx + h * k2x, my + h * k2y, mz + h * k2z)
        k4x, k4y, k4z = field(mx + dt * k3x, my + dt * k3y, mz + dt * k3z)
        s = dt / 6.0
        return (
            mx + s * (k1x + 2 * k2x + 2 * k3x + k4x),
            my + s * (k1y + 2 * k2y + 2 * k3y + k4y),
            mz + s * (k1z + 2 * k2z + 2 * k3z + k4z),
        )

    state = (m_ref[0], m_ref[1], m_ref[2])
    state = jax.lax.fori_loop(0, n_inner, lambda _, s: one_step(s), state)
    out_ref[0] = state[0]
    out_ref[1] = state[1]
    out_ref[2] = state[2]


def rk4_fused(
    m: jnp.ndarray,  # (3, N, E), N and E already padded/aligned
    w_cp: jnp.ndarray,  # (N, N)
    params: jnp.ndarray,  # (NP, E)
    dt: float,
    n_inner: int = 1,
    block_e: int = LANE,
    h_in: jnp.ndarray = None,  # (N, E) input-drive x-field; None = undriven
    interpret: bool = False,
) -> jnp.ndarray:
    _, n, e = m.shape
    assert e % block_e == 0, (e, block_e)
    if h_in is None:
        h_in = jnp.zeros((n, e), m.dtype)
    grid = (e // block_e,)
    # dt is a static compile-time constant (the paper fixes dt = 1e-11).
    kernel = functools.partial(_rk4_fused_kernel, dt=float(dt), n_inner=n_inner)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((NP, block_e), lambda i: (0, i)),  # params
            pl.BlockSpec((n, n), lambda i: (0, 0)),  # W resident
            pl.BlockSpec((n, block_e), lambda i: (0, i)),  # input drive
            pl.BlockSpec((3, n, block_e), lambda i: (0, 0, i)),  # m
        ],
        out_specs=pl.BlockSpec((3, n, block_e), lambda i: (0, 0, i)),
        out_shape=jax.ShapeDtypeStruct(m.shape, m.dtype),
        interpret=interpret,
        compiler_params=_compiler_params(),
    )(params, w_cp, h_in, m)


# ---------------------------------------------------------------------------
# Kernel 2: tiled field evaluation (+ fused stage algebra) for large N
# ---------------------------------------------------------------------------


def _field_tiled_kernel(
    params_ref, w_ref, h_ref, yx_ref, m_ref, kprev_ref, out_ref, *, stage_coef
):
    """k_new = f(m + stage_coef * k_prev) for one (N-row, E) tile.

    yx_ref holds the FULL x-plane of the stage state y (all N rows — the
    coupling needs every oscillator), computed cheaply by the caller;
    m_ref/kprev_ref hold this tile's rows of the base state and previous
    slope; h_ref this tile's rows of the input-drive x-field.
    stage_coef = 0 skips the y-algebra (k1).
    """
    p = _unpack_rows(params_ref)
    acc_t = jnp.float32 if m_ref.dtype == jnp.bfloat16 else m_ref.dtype
    # MXU: this row-block of W against the full y-x-plane. For reduced-
    # precision coupling the caller passes W pre-cast; the stage plane is
    # cast to match and the dot accumulates in the state dtype.
    yx = yx_ref[...]
    if w_ref.dtype != m_ref.dtype:
        yx = yx.astype(w_ref.dtype)
    hx = (
        p["a_cp"] * _coupling_dot(w_ref[...], yx, acc_t) + h_ref[...]
    )
    if stage_coef == 0.0:
        yx, yy, yz = m_ref[0], m_ref[1], m_ref[2]
    else:
        yx = m_ref[0] + stage_coef * kprev_ref[0]
        yy = m_ref[1] + stage_coef * kprev_ref[1]
        yz = m_ref[2] + stage_coef * kprev_ref[2]
    kx, ky, kz = _field_planes(yx, yy, yz, hx, p)
    out_ref[0] = kx
    out_ref[1] = ky
    out_ref[2] = kz


def field_tiled(
    m: jnp.ndarray,  # (3, N, E) base state tile source
    yx_full: jnp.ndarray,  # (N, E) x-plane of the stage state y
    k_prev: jnp.ndarray,  # (3, N, E) previous slope (ignored when coef=0)
    w_cp: jnp.ndarray,  # (N, N)
    params: jnp.ndarray,  # (NP, E)
    stage_coef: float,
    block_n: int = LANE,
    block_e: int = LANE,
    h_in: jnp.ndarray = None,  # (N, E) input-drive x-field; None = undriven
    interpret: bool = False,
) -> jnp.ndarray:
    _, n, e = m.shape
    assert n % block_n == 0 and e % block_e == 0, (n, e, block_n, block_e)
    if h_in is None:
        h_in = jnp.zeros((n, e), m.dtype)
    grid = (n // block_n, e // block_e)
    kernel = functools.partial(_field_tiled_kernel, stage_coef=stage_coef)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((NP, block_e), lambda i, j: (0, j)),
            pl.BlockSpec((block_n, n), lambda i, j: (i, 0)),  # W row block
            pl.BlockSpec((block_n, block_e), lambda i, j: (i, j)),  # input drive
            pl.BlockSpec((n, block_e), lambda i, j: (0, j)),  # full y-x plane
            pl.BlockSpec((3, block_n, block_e), lambda i, j: (0, i, j)),
            pl.BlockSpec((3, block_n, block_e), lambda i, j: (0, i, j)),
        ],
        out_specs=pl.BlockSpec((3, block_n, block_e), lambda i, j: (0, i, j)),
        out_shape=jax.ShapeDtypeStruct(m.shape, m.dtype),
        interpret=interpret,
        compiler_params=_compiler_params(),
    )(params, w_cp, h_in, yx_full, m, k_prev)


def _rk4_chunk_kernel(
    params_ref, w_ref, h_ref, mask_ref, m_ref, out_ref, states_ref,
    *, dt, hold_steps, k_ticks,
):
    """K serving ticks (K hold windows) for one ensemble tile, W resident.

    h_ref: (K, N, be) per-tick input-drive x-fields; mask_ref: (K, 1, be)
    f32 0/1 lane masks (False/0 = lane frozen that tick — comes back
    bit-identical, so mid-chunk admit/retire works without leaving the
    kernel); states_ref: (K, N, be) per-tick x-plane outputs (the serving
    engine's states block).
    """
    p = _unpack_rows(params_ref)
    w = w_ref[...]  # (N, N): ONE HBM->VMEM read for the whole chunk
    acc_t = jnp.float32 if m_ref.dtype == jnp.bfloat16 else m_ref.dtype

    def field(mx, my, mz, h_in):
        mx_cp = mx if w.dtype == m_ref.dtype else mx.astype(w.dtype)
        hx = p["a_cp"] * _coupling_dot(w, mx_cp, acc_t) + h_in
        return _field_planes(mx, my, mz, hx, p)

    def one_step(state, h_in):
        mx, my, mz = state
        h = dt / 2.0
        k1x, k1y, k1z = field(mx, my, mz, h_in)
        k2x, k2y, k2z = field(mx + h * k1x, my + h * k1y, mz + h * k1z, h_in)
        k3x, k3y, k3z = field(mx + h * k2x, my + h * k2y, mz + h * k2z, h_in)
        k4x, k4y, k4z = field(mx + dt * k3x, my + dt * k3y, mz + dt * k3z, h_in)
        s = dt / 6.0
        return (
            mx + s * (k1x + 2 * k2x + 2 * k3x + k4x),
            my + s * (k1y + 2 * k2y + 2 * k3y + k4y),
            mz + s * (k1z + 2 * k2z + 2 * k3z + k4z),
        )

    state = (m_ref[0], m_ref[1], m_ref[2])
    for t in range(k_ticks):  # K is small and static: unrolled over ticks
        h_in = h_ref[t]
        new = jax.lax.fori_loop(
            0, hold_steps, lambda _, s: one_step(s, h_in), state
        )
        keep = mask_ref[t] > 0.5  # (1, be) broadcasts over (N, be)
        state = tuple(jnp.where(keep, n_, o_) for n_, o_ in zip(new, state))
        states_ref[t] = state[0]
    out_ref[0] = state[0]
    out_ref[1] = state[1]
    out_ref[2] = state[2]


def rk4_chunk(
    m: jnp.ndarray,  # (3, N, E), N and E already padded/aligned
    w_cp: jnp.ndarray,  # (N, N); may be pre-cast (reduced-precision coupling)
    params: jnp.ndarray,  # (NP, E)
    dt: float,
    hold_steps: int,
    h_block: jnp.ndarray,  # (K, N, E) per-tick input-drive x-fields
    mask_block: jnp.ndarray,  # (K, E) f32 0/1 per-tick lane masks
    block_e: int = LANE,
    interpret: bool = False,
):
    """The chunk-resident serving kernel: K ticks x hold_steps x 4 stages
    in one launch, W and state planes VMEM-resident for the whole chunk.

    Returns (m' (3, N, E), states (K, N, E) per-tick x-planes).
    """
    _, n, e = m.shape
    k_ticks = h_block.shape[0]
    assert e % block_e == 0, (e, block_e)
    assert h_block.shape == (k_ticks, n, e), (h_block.shape, (k_ticks, n, e))
    grid = (e // block_e,)
    kernel = functools.partial(
        _rk4_chunk_kernel,
        dt=float(dt), hold_steps=hold_steps, k_ticks=k_ticks,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((NP, block_e), lambda i: (0, i)),  # params
            pl.BlockSpec((n, n), lambda i: (0, 0)),  # W resident per chunk
            pl.BlockSpec((k_ticks, n, block_e), lambda i: (0, 0, i)),  # inputs
            pl.BlockSpec((k_ticks, 1, block_e), lambda i: (0, 0, i)),  # masks
            pl.BlockSpec((3, n, block_e), lambda i: (0, 0, i)),  # m
        ],
        out_specs=[
            pl.BlockSpec((3, n, block_e), lambda i: (0, 0, i)),
            pl.BlockSpec((k_ticks, n, block_e), lambda i: (0, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(m.shape, m.dtype),
            jax.ShapeDtypeStruct((k_ticks, n, e), m.dtype),
        ],
        interpret=interpret,
        compiler_params=_compiler_params(),
    )(params, w_cp, h_block, mask_block.reshape(k_ticks, 1, e), m)


def rk4_tiled_step(
    m: jnp.ndarray,
    w_cp: jnp.ndarray,
    params: jnp.ndarray,
    dt: float,
    block_n: int = LANE,
    block_e: int = LANE,
    h_in: jnp.ndarray = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """One RK4 step built from four tiled field-kernel launches.

    The per-stage x-plane updates (y^x = m^x + c k^x) are O(N E) elementwise
    XLA ops — negligible next to the O(N^2 E) in-kernel coupling.
    """
    dt = float(dt)  # static: baked into the stage kernels
    f = functools.partial(
        field_tiled,
        w_cp=w_cp,
        params=params,
        block_n=block_n,
        block_e=block_e,
        h_in=h_in,
        interpret=interpret,
    )
    zeros = jnp.zeros_like(m)
    k1 = f(m, m[0], zeros, stage_coef=0.0)
    k2 = f(m, m[0] + (0.5 * dt) * k1[0], k1, stage_coef=0.5 * dt)
    k3 = f(m, m[0] + (0.5 * dt) * k2[0], k2, stage_coef=0.5 * dt)
    k4 = f(m, m[0] + dt * k3[0], k3, stage_coef=dt)
    return m + (dt / 6.0) * (k1 + 2.0 * k2 + 2.0 * k3 + k4)
