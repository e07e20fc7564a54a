"""Pure-jnp oracles for every kernel in this package.

Kernel-side state layout (TPU-friendly):
    m        : (3, N, E)  — component-major so each component is a (N, E)
                            VREG-tileable plane; E is the MXU lane dimension.
    w_cp     : (N, N)
    params   : (NP, E)    — per-ensemble-member scalar parameters, VMEM-
                            resident (enables parameter sweeps inside the
                            kernel without re-compilation).

PARAM_LAYOUT defines the packing order shared by kernels and oracles.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.constants import EXACT_MATMUL, STOParams

PARAM_LAYOUT: Tuple[str, ...] = (
    "pref",  # gamma / (1 + alpha^2)
    "alpha",
    "hs_coef",  # H_s numerator [Oe]
    "lam",
    "happl",
    "demag",  # Hk - 4 pi Ms
    "a_cp",
    "px",
    "py",
    "pz",
)
NP = len(PARAM_LAYOUT)


def pack_params(params: STOParams, e: int, dtype=jnp.float32) -> jnp.ndarray:
    """Pack STOParams into the kernel's (NP, E) layout.

    Accepts scalar leaves or (E, 1)-ensemble leaves (from
    `ensemble.broadcast_params`).
    """
    vals = {
        "pref": params.llg_prefactor,
        "alpha": params.alpha,
        "hs_coef": params.hs_coef,
        "lam": params.lam,
        "happl": params.happl,
        "demag": params.demag_field,
        "a_cp": params.a_cp,
        "px": params.px,
        "py": params.py,
        "pz": params.pz,
    }
    rows = []
    for name in PARAM_LAYOUT:
        v = jnp.asarray(vals[name], dtype=dtype).reshape(-1)  # () or (E,)
        rows.append(jnp.broadcast_to(v, (e,)))
    return jnp.stack(rows, axis=0)


def _unpack(pvec: jnp.ndarray):
    """(NP, E) -> dict of (E,) rows (or (NP,) -> scalars)."""
    return {name: pvec[i] for i, name in enumerate(PARAM_LAYOUT)}


def llg_field_planes(m, w_cp, pvec, h_in=None):
    """Oracle vector field in kernel layout.

    m: (3, N, E); w_cp: (N, N); pvec: (NP, E). Returns k: (3, N, E).
    h_in: optional (N, E) input-drive x-field A_in (W^in u), added to the
    coupling field (input is held piecewise-constant over a hold window, so
    it enters the field as a constant plane).
    This is algebraically identical to core.sto.llg_field — the equivalence
    is itself asserted by tests/test_kernels_sto.py.

    Precision policy (ExecPlan.precision): callers opt into the reduced-
    precision coupling GEMM by passing w_cp ALREADY cast (e.g. bf16, cast
    once outside the integration loop, not per stage). A w_cp dtype that
    differs from the state dtype makes the coupling dot consume reduced
    operands while accumulating in the state dtype; everything else — the
    elementwise LLG math, the state carry, the RK4 combine — stays in the
    state dtype. When dtypes match (the default), this path is untouched
    and bit-exact.
    """
    p = _unpack(pvec)
    mx, my, mz = m[0], m[1], m[2]  # (N, E)
    # coupling: rows of W against the x-plane -> (N, E) matmul on the MXU
    mx_cp = mx if w_cp.dtype == m.dtype else mx.astype(w_cp.dtype)
    hx = p["a_cp"] * jnp.dot(
        w_cp, mx_cp, preferred_element_type=m.dtype, precision=EXACT_MATMUL
    )
    if h_in is not None:
        hx = hx + h_in
    hz = p["happl"] + p["demag"] * mz
    mdotp = p["px"] * mx + p["py"] * my + p["pz"] * mz
    hs = p["hs_coef"] / (1.0 + p["lam"] * mdotp)
    # b = H + hs * (p x m)
    bx = hx + hs * (p["py"] * mz - p["pz"] * my)
    by = hs * (p["pz"] * mx - p["px"] * mz)
    bz = hz + hs * (p["px"] * my - p["py"] * mx)
    # m x b
    cx = my * bz - mz * by
    cy = mz * bx - mx * bz
    cz = mx * by - my * bx
    # m x (m x b)
    dx = my * cz - mz * cy
    dy = mz * cx - mx * cz
    dz = mx * cy - my * cx
    pref = p["pref"]
    al = p["alpha"]
    kx = -pref * cx - al * pref * dx
    ky = -pref * cy - al * pref * dy
    kz = -pref * cz - al * pref * dz
    return jnp.stack([kx, ky, kz], axis=0)


def rk4_step_planes(m, w_cp, pvec, dt, h_in=None):
    """One classical RK4 step in kernel layout (oracle)."""
    k1 = llg_field_planes(m, w_cp, pvec, h_in)
    k2 = llg_field_planes(m + 0.5 * dt * k1, w_cp, pvec, h_in)
    k3 = llg_field_planes(m + 0.5 * dt * k2, w_cp, pvec, h_in)
    k4 = llg_field_planes(m + dt * k3, w_cp, pvec, h_in)
    return m + (dt / 6.0) * (k1 + 2.0 * k2 + 2.0 * k3 + k4)


def rk4_multi_step_planes(m, w_cp, pvec, dt, n_inner: int, h_in=None):
    """n_inner fused RK4 steps (oracle for the VMEM-resident kernel)."""

    def body(_, mm):
        return rk4_step_planes(mm, w_cp, pvec, dt, h_in)

    return jax.lax.fori_loop(0, n_inner, body, m)


def rk4_chunk_planes(
    m,  # (3, N, E) state
    w_cp,  # (N, N) — pre-cast by the caller for reduced-precision coupling
    pvec,  # (NP, E)
    dt,
    hold_steps: int,
    h_block,  # (K, N, E) per-tick input-drive x-fields
    mask_block,  # (K, E) bool; False = lane frozen that tick
):
    """Chunk-resident K-tick integration: the oracle behind impl="chunk".

    The whole K-tick x hold_steps x 4-stage RK4 loop runs as ONE traced
    region: the per-tick input fields arrive as a precomputed (K, N, E)
    block (one input GEMM per chunk instead of one per tick), W is read by
    every stage from the same (optionally reduced-precision) operand cast
    exactly once by the caller, and the per-tick states block (K, N, E)
    stays device-side for the serving engine's bulk harvest. Per-element
    float op order matches the per-tick ref path (`rk4_step_planes` +
    masked where), so precision=None chunks agree with the ref impl to the
    bit on CPU. Returns (m' (3, N, E), states (K, N, E)).

    On TPU the same loop structure is a Pallas kernel
    (`kernels.sto_step.rk4_chunk`) that keeps the state planes VMEM-
    resident and reads W from HBM once per chunk per ensemble tile instead
    of once per tick.
    """
    dt_c = jnp.asarray(dt, m.dtype)

    def per_tick(mm, tick_in):
        h_t, mask_t = tick_in

        def inner(mi, _):
            return rk4_step_planes(mi, w_cp, pvec, dt_c, h_t), None

        m_new, _ = jax.lax.scan(inner, mm, None, length=hold_steps)
        m_new = jnp.where(mask_t[None, None, :], m_new, mm)
        return m_new, m_new[0]

    mT, states = jax.lax.scan(per_tick, m, (h_block, mask_block))
    return mT, states  # (3, N, E), (K, N, E)


# ---------------------------------------------------------------------------
# Physics families (SimSpec.topology) — planes-layout chunk bodies
# ---------------------------------------------------------------------------


def rk4_chunk_planes_window(
    m,  # (3, N, E) state
    w_cp,  # (N, N) — pre-cast by the caller for reduced-precision coupling
    pvec,  # (NP, E)
    dt,
    hold_steps: int,
    readout_window: int,
    h_block,  # (K, N, E) per-tick input-drive x-fields
    mask_block,  # (K, E) bool; False = lane frozen that tick
):
    """topology="array_transient" chunk body (Kanao et al., arXiv:1905.07937).

    Identical coupled-array dynamics to `rk4_chunk_planes`; only the
    emitted per-tick state differs — the mean of the m_x plane over the
    LAST `readout_window` RK substeps of the hold window (the transient the
    array readout samples), instead of the endpoint alone. The hold window
    is split (hold_steps - w) + w with the same per-step op sequence, so
    readout_window=1 is bit-identical to the coupled_array chunk body.
    Returns (m' (3, N, E), states (K, N, E)).
    """
    dt_c = jnp.asarray(dt, m.dtype)
    w = int(readout_window)

    def per_tick(mm, tick_in):
        h_t, mask_t = tick_in

        def inner(mi, _):
            return rk4_step_planes(mi, w_cp, pvec, dt_c, h_t), None

        m_mid = mm
        if hold_steps > w:
            m_mid, _ = jax.lax.scan(inner, mm, None, length=hold_steps - w)

        def tail(mi, _):
            mi2 = rk4_step_planes(mi, w_cp, pvec, dt_c, h_t)
            return mi2, mi2[0]

        m_new, xs = jax.lax.scan(tail, m_mid, None, length=w)  # xs (w, N, E)
        state = jnp.mean(xs, axis=0) if w > 1 else xs[0]
        m_new = jnp.where(mask_t[None, None, :], m_new, mm)
        state = jnp.where(mask_t[None, :], state, mm[0])
        return m_new, state

    mT, states = jax.lax.scan(per_tick, m, (h_block, mask_block))
    return mT, states  # (3, N, E), (K, N, E)


def tm_chunk_planes(
    m,  # (3, N, E) virtual-node snapshots; row N-1 carries the oscillator
    w_cp,  # (N, N) feedback mixing — pre-cast for reduced-precision coupling
    pvec,  # (NP, E)
    dt,
    hold_steps: int,
    h_block,  # (K, N, E) per-tick masked-input x-fields A_in (W^in u)
    mask_block,  # (K, E) bool; False = lane frozen that tick
):
    """topology="time_multiplexed" chunk body (Riou et al., arXiv:1904.11236).

    ONE physical oscillator per lane; N virtual nodes are its snapshots at
    the ends of consecutive hold windows. Per tick the total per-node drive
    is two GEMMs — the masked input field (precomputed h_block) plus the
    delayed feedback a_cp * (W^cp @ x_prev), where x_prev is the PREVIOUS
    tick's snapshot x-plane (w_cp=I is the classic delay-line
    self-feedback) — and then the INNER SCAN IS THE DELAY LINE: sequential
    over the N virtual nodes (each integrating the carried (3, E)
    oscillator state hold_steps RK substeps under its scalar-per-lane
    drive), trivially parallel across ensemble lanes. The reduced-precision
    coupling policy maps onto the feedback GEMM exactly as it maps onto the
    array coupling GEMM. Returns (m' (3, N, E), states (K, N, E)).
    """
    dt_c = jnp.asarray(dt, m.dtype)
    n = m.shape[1]
    p = _unpack(pvec)
    w_zero = jnp.zeros((1, 1), m.dtype)  # single oscillator: no array coupling

    def per_tick(mm, tick_in):
        h_ext_t, mask_t = tick_in
        x_prev = mm[0]  # (N, E) previous tick's snapshots
        x_cp = x_prev if w_cp.dtype == mm.dtype else x_prev.astype(w_cp.dtype)
        h_t = h_ext_t + p["a_cp"] * jnp.dot(
            w_cp, x_cp, preferred_element_type=mm.dtype, precision=EXACT_MATMUL
        )  # (N, E)
        s0 = mm[:, n - 1 : n, :]  # carried oscillator state (3, 1, E)

        def per_node(s, h_row):  # h_row (E,) — virtual node's drive
            h_j = h_row[None, :]  # (1, E)

            def inner(si, _):
                return rk4_step_planes(si, w_zero, pvec, dt_c, h_j), None

            s_new, _ = jax.lax.scan(inner, s, None, length=hold_steps)
            return s_new, s_new[:, 0, :]  # snapshot (3, E)

        sT, snaps = jax.lax.scan(per_node, s0, h_t)  # snaps (N, 3, E)
        m_new = jnp.transpose(snaps, (1, 0, 2))  # (3, N, E)
        m_new = jnp.where(mask_t[None, None, :], m_new, mm)
        return m_new, m_new[0]

    mT, states = jax.lax.scan(per_tick, m, (h_block, mask_block))
    return mT, states  # (3, N, E), (K, N, E)


# ---------------------------------------------------------------------------
# Flash-attention oracle (LM substrate)
# ---------------------------------------------------------------------------


def mha_reference(q, k, v, causal: bool = True, scale=None, window: int = 0):
    """Plain softmax attention. q,k,v: (B, H, S, D) -> (B, H, S, D).

    window > 0 restricts keys to [i - window + 1, i] (sliding-window attn).
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    sq, sk = q.shape[-2], k.shape[-2]
    qi = jnp.arange(sq)[:, None] + (sk - sq)  # align last q with last k
    ki = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), dtype=bool)
    if causal:
        mask &= ki <= qi
    if window > 0:
        mask &= ki > qi - window
    logits = jnp.where(mask, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v).astype(q.dtype)
