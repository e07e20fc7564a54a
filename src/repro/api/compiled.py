"""compile_plan(SimSpec, ExecPlan) -> CompiledSim: the one execution surface.

All impl dispatch, padding, ensemble batching, and sharding decisions are
made HERE, once, at plan compilation:

  - "auto" impls resolve through `kernels.ops.choose_impl`, which consults
    the measured-latency dispatch table — in-process measurements first,
    then the persisted per-platform JSON (`kernels/dispatch_table.py`,
    seeded from BENCH_serve.json) — before the platform gate / VMEM
    heuristic. `ExecPlan(measure=True)` times the candidates for this
    (N, E) first and pins the winner.
  - mesh plans lower the same physics through shard_map with the
    PartitionSpecs from `distributed.sharding.reservoir_specs`.

The jit-cached entry points on the returned CompiledSim:

  drive(u, m0=None)            solo reservoir over an input series
  drive_batch(U, m0=None)      E lanes over shared or per-lane series
  integrate(n_steps, ...)      free-run (u = 0) ensemble integration
  tick(m, u, lane_mask=None)   ONE hold window for a slot batch — the
                               serving engine's per-tick path
  tick_chunk(m, U, ...)        K hold windows in one dispatch — the chunked
                               serving hot path; with ExecPlan(learn="rls")
                               it also trains per-lane readouts online
                               (targets/learn_state/learn_mask kwargs)

All jit'd workers are module-level, so every CompiledSim for the same
(static-shape, impl) signature shares one compilation.

Numerical contract (pinned by tests/test_api_plan.py and
tests/test_precision_chunk.py): impl="scan" runs the exact op sequence of
the legacy `reservoir.drive` / `ensemble.integrate_ensemble` paths
(bit-identical results); the planes impls ("ref"/"fused"/"tiled"/"chunk")
and sharded plans agree within the kernel test suite's tolerance (on CPU,
"chunk" is bit-identical to "ref"). `ExecPlan.precision` None/"highest"
plans trace the identical graph they did before the field existed;
"bf16_coupling"/"mixed" reduce only the coupling/input GEMMs (f32 state
carry, f32 RK4 accumulation) and are guarded by the NARMA-10 NMSE
tolerance test.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import integrators, sto
from repro.core.constants import EXACT_MATMUL, STOParams
from repro.kernels import ops
from repro.kernels import ref as kref
from repro.kernels import rls as krls

from repro.api.plan import ExecPlan, check_plan_supports_topology
from repro.api.spec import SimSpec
from repro.api import sharded as _sharded

PLANES_IMPLS = ("ref", "fused", "tiled", "chunk")


# ---------------------------------------------------------------------------
# jit'd workers — core (E, N, 3) layout ("scan" impl; legacy-exact math)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("hold_steps", "tableau_name"))
def _drive_scan(
    params: STOParams,  # scalar leaves
    w_cp: jnp.ndarray,
    w_in: jnp.ndarray,
    m0: jnp.ndarray,  # (N, 3)
    u_seq: jnp.ndarray,  # (T, N_in)
    dt,
    hold_steps: int,
    tableau_name: str = "rk4",
):
    """Solo drive — the op sequence formerly in core/reservoir._drive_scan,
    moved verbatim so the legacy `drive` shim stays bit-exact."""
    tableau = integrators.TABLEAUX[tableau_name]

    def field(m, h_in_x):
        return sto.llg_field(m, params, w_cp, h_in_x)

    step = integrators.make_step(field, tableau)
    dt = jnp.asarray(dt, dtype=m0.dtype)

    def per_sample(m, u_t):
        # Input held piecewise-constant over the hold window (paper: the
        # input signal is a discrete-point series).
        h_in_x = params.a_in * jnp.matmul(
            w_in, u_t, precision=EXACT_MATMUL
        )  # (N,)

        def inner(mi, _):
            return step(mi, dt, h_in_x), None

        m, _ = jax.lax.scan(inner, m, None, length=hold_steps)
        return m, m[..., 0]  # node states: x-components (paper §3.1)

    mT, states = jax.lax.scan(per_sample, m0, u_seq)
    return mT, states  # states: (T, N)


@functools.partial(jax.jit, static_argnames=("hold_steps", "tableau_name"))
def _drive_scan_batch(
    params_e: STOParams,  # leaves (E, 1)
    w_cp: jnp.ndarray,
    w_in: jnp.ndarray,
    m0_e: jnp.ndarray,  # (E, N, 3)
    u_seq_e: jnp.ndarray,  # (T, E, N_in)
    dt,
    hold_steps: int,
    tableau_name: str = "rk4",
):
    """Ensemble drive in the core layout (per-lane params and inputs)."""
    tableau = integrators.TABLEAUX[tableau_name]

    def field(m, h_in_x):
        return sto.llg_field(m, params_e, w_cp, h_in_x)

    step = integrators.make_step(field, tableau)
    dt = jnp.asarray(dt, dtype=m0_e.dtype)

    def per_sample(m, u_t):
        h_in = params_e.a_in * jnp.einsum(
            "ni,ei->en", w_in, u_t, precision=EXACT_MATMUL
        )  # (E, N)

        def inner(mi, _):
            return step(mi, dt, h_in), None

        m, _ = jax.lax.scan(inner, m, None, length=hold_steps)
        return m, m[..., 0]

    mT, states = jax.lax.scan(per_sample, m0_e, u_seq_e)
    return mT, states  # (E, N, 3), (T, E, N)


@functools.partial(jax.jit, static_argnames=("hold_steps", "tableau_name"))
def _tick_scan(params_e, w_cp, w_in, m_planes, u, mask, dt, hold_steps,
               tableau_name: str = "rk4"):
    """Advance all E slots one input tick in the core (E, N, 3) layout.

    Takes/returns the slot store's (3, N, E) planes — the layout shuffle
    lives inside the jit so one dispatch covers the whole tick. The
    integration mirrors `_drive_scan`'s per_sample exactly (same field, same
    step, same op order per lane) so scan-impl serving reproduces solo
    drive() results; masked (idle) lanes return unchanged.
    """
    m = jnp.transpose(m_planes, (2, 1, 0))  # (E, N, 3)
    h_in = params_e.a_in * jnp.einsum(
        "ni,ei->en", w_in, u, precision=EXACT_MATMUL
    )  # (E, N)

    def field(mm, h):
        return sto.llg_field(mm, params_e, w_cp, h)

    step = integrators.make_step(field, integrators.TABLEAUX[tableau_name])

    def inner(mi, _):
        return step(mi, dt, h_in), None

    m_new, _ = jax.lax.scan(inner, m, None, length=hold_steps)
    m_new = jnp.where(mask[:, None, None], m_new, m)
    return jnp.transpose(m_new, (2, 1, 0)), jnp.transpose(m_new[..., 0])


@functools.partial(jax.jit, static_argnames=("hold_steps", "tableau_name"))
def _tick_chunk_scan(params_e, w_cp, w_in, m_planes, u_block, mask_block, dt,
                     hold_steps, tableau_name: str = "rk4"):
    """Advance all E slots through K input ticks in ONE dispatch (core layout).

    u_block is (K, E, N_in), mask_block (K, E). The per-tick body is exactly
    `_tick_scan`'s (same h_in einsum, same hold-window scan, same masked
    jnp.where) with the layout shuffle hoisted out of the K-loop — transposes
    are pure data movement, so a K-chunk is bit-identical to K sequential
    `_tick_scan` calls. The stacked states live on device until the caller
    transfers them: (K, N, E) states block, one host copy per chunk instead
    of per tick.
    """
    m = jnp.transpose(m_planes, (2, 1, 0))  # (E, N, 3)

    def field(mm, h):
        return sto.llg_field(mm, params_e, w_cp, h)

    step = integrators.make_step(field, integrators.TABLEAUX[tableau_name])

    def per_tick(m_c, tick_in):
        u_t, mask_t = tick_in
        h_in = params_e.a_in * jnp.einsum(
            "ni,ei->en", w_in, u_t, precision=EXACT_MATMUL
        )  # (E, N)

        def inner(mi, _):
            return step(mi, dt, h_in), None

        m_new, _ = jax.lax.scan(inner, m_c, None, length=hold_steps)
        m_new = jnp.where(mask_t[:, None, None], m_new, m_c)
        return m_new, jnp.transpose(m_new[..., 0])  # (N, E)

    mT, states = jax.lax.scan(per_tick, m, (u_block, mask_block))
    return jnp.transpose(mT, (2, 1, 0)), states  # (3, N, E), (K, N, E)


def _learn_chunk_tail(states, y_block, lmask_block, p0, w0, lam):
    """Shared learn tail: states block (K, N, E) -> chunked RLS update.

    Builds the (K, E, S) feature block (node states + bias) and applies
    `kernels.rls.rls_chunk` — the whole chunk's sequential gain/weight
    recursion with O(1) full-P passes. Runs inside the workers' jit, so a
    learning chunk is still ONE dispatch with zero extra host round-trips.
    """
    xb = jnp.concatenate(
        [
            jnp.transpose(states, (0, 2, 1)),  # (K, E, N)
            jnp.ones((states.shape[0], states.shape[2], 1), states.dtype),
        ],
        axis=-1,
    )
    return krls.rls_chunk(p0, w0, xb, y_block, lmask_block, lam)


@functools.partial(
    jax.jit, static_argnames=("lam", "hold_steps", "tableau_name")
)
def _tick_chunk_scan_rls(params_e, w_cp, w_in, m_planes, u_block, mask_block,
                         y_block, lmask_block, p0, w0, lam, dt, hold_steps,
                         tableau_name: str = "rk4"):
    """`_tick_chunk_scan` + the chunked RLS readout update, one dispatch
    (ExecPlan.learn="rls", core layout).

    The integration scan is exactly `_tick_chunk_scan`'s — m and the states
    block are bit-identical to the inference-only chunk — and the chunk's
    states then feed `kernels.rls.rls_chunk`: the full K-tick sequential
    RLS gain recursion applied with ~3 full-P traversals per CHUNK (not per
    tick). lmask_block (K, E) gates which lanes learn which ticks (False =
    P/W value-frozen: idle slots, washout ticks, inference-only tenants).
    Returns (m' (3, N, E), states (K, N, E), P', W', preds (K, E, n_out))
    with preds the a-priori (pre-update) per-tick predictions.
    """
    mT, states = _tick_chunk_scan(
        params_e, w_cp, w_in, m_planes, u_block, mask_block, dt, hold_steps,
        tableau_name,
    )
    pT, wT, preds = _learn_chunk_tail(states, y_block, lmask_block, p0, w0, lam)
    return mT, states, pT, wT, preds


def _lms_chunk_tail(states, y_block, lmask_block, w0, mu):
    """Shared LMS learn tail: states block (K, N, E) -> chunked NLMS update.

    Same feature construction as `_learn_chunk_tail` (node states + bias),
    applied through `kernels.rls.lms_chunk` — O(S) per tick, no P block.
    """
    xb = jnp.concatenate(
        [
            jnp.transpose(states, (0, 2, 1)),  # (K, E, N)
            jnp.ones((states.shape[0], states.shape[2], 1), states.dtype),
        ],
        axis=-1,
    )
    return krls.lms_chunk(w0, xb, y_block, lmask_block, mu)


@functools.partial(
    jax.jit, static_argnames=("mu", "hold_steps", "tableau_name")
)
def _tick_chunk_scan_lms(params_e, w_cp, w_in, m_planes, u_block, mask_block,
                         y_block, lmask_block, w0, mu, dt, hold_steps,
                         tableau_name: str = "rk4"):
    """`_tick_chunk_scan` + the chunked NLMS readout update, one dispatch
    (ExecPlan.learn="lms", core layout). Identical integration to the
    inference-only chunk; the learn tail carries only the (E, S, n_out)
    weight lanes — no inverse-Gram block rides the dispatch.
    Returns (m' (3, N, E), states (K, N, E), W', preds (K, E, n_out))."""
    mT, states = _tick_chunk_scan(
        params_e, w_cp, w_in, m_planes, u_block, mask_block, dt, hold_steps,
        tableau_name,
    )
    wT, preds = _lms_chunk_tail(states, y_block, lmask_block, w0, mu)
    return mT, states, wT, preds


# ---------------------------------------------------------------------------
# jit'd workers — kernel (3, N, E) planes layout ("ref"/"fused"/"tiled"/"chunk")
# ---------------------------------------------------------------------------


def _input_field(w_in, u, a_in, precision):
    """h_in = A_in * (W^in u) per lane, honoring the precision policy.

    "mixed" runs this GEMM — the 'field GEMM' of ExecPlan.precision — on
    bf16 operands with accumulation in the state dtype; every other policy
    keeps the exact op sequence the workers have always traced. u may be a
    single tick (E, N_in) or a chunk block (K, E, N_in).
    """
    eq = "ni,ei->ne" if u.ndim == 2 else "ni,kei->kne"
    scale = a_in[None, :] if u.ndim == 2 else a_in[None, None, :]
    return ops.input_field_einsum(eq, w_in, u, precision) * scale


@functools.partial(
    jax.jit,
    static_argnames=("dt", "hold_steps", "impl", "n_inner", "block_n", "block_e", "interpret", "precision"),
)
def _drive_planes(
    params_e, w_cp, w_in, m0_planes, u_seq_e,
    *, dt, hold_steps, impl, n_inner, block_n, block_e, interpret,
    precision="highest",
):
    """Ensemble drive through the kernel layout: per input sample, one
    hold-window integrate with the resolved impl."""
    e = m0_planes.shape[-1]
    pv = kref.pack_params(params_e, e, m0_planes.dtype)
    a_in = jnp.reshape(params_e.a_in, (-1,)) * jnp.ones((e,), m0_planes.dtype)

    def per_sample(m, u_t):  # u_t: (E, N_in)
        h = _input_field(w_in, u_t, a_in, precision)
        m = ops._integrate_planes_jit(
            m, w_cp, pv, h, None,
            dt=dt, n_steps=hold_steps, impl=impl, n_inner=n_inner,
            block_n=block_n, block_e=block_e, interpret=interpret,
            precision=precision,
        )
        return m, m[0]

    mT, states = jax.lax.scan(per_sample, m0_planes, u_seq_e)
    return mT, jnp.transpose(states, (0, 2, 1))  # (3, N, E), (T, E, N)


@functools.partial(
    jax.jit,
    static_argnames=("dt", "hold_steps", "impl", "n_inner", "block_n", "block_e", "interpret", "precision"),
)
def _tick_planes(
    params_e, w_cp, w_in, m_planes, u, mask,
    *, dt, hold_steps, impl, n_inner, block_n, block_e, interpret,
    precision="highest",
):
    """One hold window for a slot batch in the kernel layout; masked lanes
    come back bit-identical (partial-batch masking in kernels/ops.py)."""
    e = m_planes.shape[-1]
    pv = kref.pack_params(params_e, e, m_planes.dtype)
    a_in = jnp.reshape(params_e.a_in, (-1,)) * jnp.ones((e,), m_planes.dtype)
    h = _input_field(w_in, u, a_in, precision)
    m_new = ops._integrate_planes_jit(
        m_planes, w_cp, pv, h, mask,
        dt=dt, n_steps=hold_steps, impl=impl, n_inner=n_inner,
        block_n=block_n, block_e=block_e, interpret=interpret,
        precision=precision,
    )
    return m_new, m_new[0]


@functools.partial(
    jax.jit,
    static_argnames=("dt", "hold_steps", "impl", "n_inner", "block_n", "block_e", "interpret", "precision"),
)
def _tick_chunk_planes(
    params_e, w_cp, w_in, m_planes, u_block, mask_block,
    *, dt, hold_steps, impl, n_inner, block_n, block_e, interpret,
    precision="highest",
):
    """K serving ticks in one dispatch, kernel layout.

    For the per-window impls (ref/fused/tiled) the per-tick body is
    `_tick_planes`' exactly, with pack_params hoisted out of the K-loop (it
    is value-identical each tick). impl="chunk" is the chunk-resident path:
    the whole (K, N, E) input-field block is computed with ONE GEMM per
    chunk and handed to `ops.sto_rk4_tick_chunk_planes`' worker, which runs
    the K x hold_steps x 4-stage loop as one resident region (the Pallas
    rk4_chunk kernel on TPU). Returns ((3, N, E), (K, N, E))."""
    e = m_planes.shape[-1]
    pv = kref.pack_params(params_e, e, m_planes.dtype)
    a_in = jnp.reshape(params_e.a_in, (-1,)) * jnp.ones((e,), m_planes.dtype)

    if impl == "chunk":
        h_block = _input_field(w_in, u_block, a_in, precision)  # (K, N, E)
        return ops._tick_chunk_planes_jit(
            m_planes, w_cp, pv, h_block, mask_block,
            dt=dt, hold_steps=hold_steps, impl=impl, n_inner=n_inner,
            block_n=block_n, block_e=block_e, interpret=interpret,
            precision=precision,
        )

    def per_tick(m_c, tick_in):
        u_t, mask_t = tick_in
        h = _input_field(w_in, u_t, a_in, precision)
        m_new = ops._integrate_planes_jit(
            m_c, w_cp, pv, h, mask_t,
            dt=dt, n_steps=hold_steps, impl=impl, n_inner=n_inner,
            block_n=block_n, block_e=block_e, interpret=interpret,
            precision=precision,
        )
        return m_new, m_new[0]

    mT, states = jax.lax.scan(per_tick, m_planes, (u_block, mask_block))
    return mT, states  # (3, N, E), (K, N, E)


@functools.partial(
    jax.jit,
    static_argnames=("lam", "dt", "hold_steps", "impl", "n_inner", "block_n", "block_e", "interpret", "precision"),
)
def _tick_chunk_planes_rls(
    params_e, w_cp, w_in, m_planes, u_block, mask_block, y_block, lmask_block,
    p0, w0, *, lam, dt, hold_steps, impl, n_inner, block_n, block_e, interpret,
    precision="highest",
):
    """`_tick_chunk_planes` + the chunked RLS readout update, one dispatch
    (ExecPlan.learn="rls", kernel layout). The integrate may be a Pallas
    kernel; the learn tail is the same jnp `kernels.rls.rls_chunk` either
    way, applied to the chunk's (K, N, E) states block + bias. The learn
    recursion always runs in the state dtype — reduced precision stops at
    the readout-learning boundary (P's conditioning; see kernels/rls.py)."""
    mT, states = _tick_chunk_planes(
        params_e, w_cp, w_in, m_planes, u_block, mask_block,
        dt=dt, hold_steps=hold_steps, impl=impl, n_inner=n_inner,
        block_n=block_n, block_e=block_e, interpret=interpret,
        precision=precision,
    )
    pT, wT, preds = _learn_chunk_tail(states, y_block, lmask_block, p0, w0, lam)
    return mT, states, pT, wT, preds  # (3,N,E), (K,N,E), P', W', (K,E,n_out)


@functools.partial(
    jax.jit,
    static_argnames=("mu", "dt", "hold_steps", "impl", "n_inner", "block_n", "block_e", "interpret", "precision"),
)
def _tick_chunk_planes_lms(
    params_e, w_cp, w_in, m_planes, u_block, mask_block, y_block, lmask_block,
    w0, *, mu, dt, hold_steps, impl, n_inner, block_n, block_e, interpret,
    precision="highest",
):
    """`_tick_chunk_planes` + the chunked NLMS readout update, one dispatch
    (ExecPlan.learn="lms", kernel layout). Like the RLS twin, the learn tail
    always runs in the state dtype — reduced precision stops at the
    readout-learning boundary."""
    mT, states = _tick_chunk_planes(
        params_e, w_cp, w_in, m_planes, u_block, mask_block,
        dt=dt, hold_steps=hold_steps, impl=impl, n_inner=n_inner,
        block_n=block_n, block_e=block_e, interpret=interpret,
        precision=precision,
    )
    wT, preds = _lms_chunk_tail(states, y_block, lmask_block, w0, mu)
    return mT, states, wT, preds  # (3,N,E), (K,N,E), W', (K,E,n_out)


@functools.partial(
    jax.jit,
    static_argnames=("dt", "n_steps", "save_every", "impl", "n_inner", "block_n", "block_e", "interpret", "precision"),
)
def _integrate_planes(
    params_e, w_cp, m0_planes,
    *, dt, n_steps, save_every, impl, n_inner, block_n, block_e, interpret,
    precision="highest",
):
    """Free-run (u = 0) integration in the kernel layout."""
    e = m0_planes.shape[-1]
    pv = kref.pack_params(params_e, e, m0_planes.dtype)

    def chunk(m, length):
        return ops._integrate_planes_jit(
            m, w_cp, pv, None, None,
            dt=dt, n_steps=length, impl=impl, n_inner=n_inner,
            block_n=block_n, block_e=block_e, interpret=interpret,
            precision=precision,
        )

    if not save_every:
        return chunk(m0_planes, n_steps), None

    def body(m, _):
        m = chunk(m, save_every)
        return m, m

    mT, traj = jax.lax.scan(body, m0_planes, None, length=n_steps // save_every)
    return mT, traj


# ---------------------------------------------------------------------------
# jit'd workers — physics families (SimSpec.topology != "coupled_array")
#
# One chunk worker per layout covers every family: topology/readout_window
# are static arguments, so each family specializes its own executable while
# sharing this single code path (the family analogue of the "capabilities
# are fields, not entry points" rule). The coupled_array workers above are
# untouched — family dispatch happens in CompiledSim, so pre-family plans
# trace the identical graphs they always did.
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit,
    static_argnames=("topology", "readout_window", "hold_steps", "tableau_name"),
)
def _tick_chunk_scan_family(
    params_e, w_cp, w_in, m_planes, u_block, mask_block, dt,
    *, topology, readout_window, hold_steps, tableau_name="rk4",
):
    """K-tick family chunk in the core (E, N, 3) layout — the family oracle.

    topology="array_transient": `_tick_chunk_scan`'s coupled dynamics with
    the hold window split (hold_steps - w) + w and the emitted state the
    mean of the last w substeps' x-components — the same per-step op
    sequence, so readout_window=1 is bit-identical to `_tick_chunk_scan`.

    topology="time_multiplexed": one physical oscillator per lane
    (uncoupled core field, w_cp=None); the inner scan over the N virtual
    nodes is the delay line. Per tick the node drives are the masked input
    field plus the delayed feedback a_cp * (W^cp @ x_prev) from the
    previous tick's snapshots; row j of the state is node j's snapshot.
    """
    m = jnp.transpose(m_planes, (2, 1, 0))  # (E, N, 3)
    tableau = integrators.TABLEAUX[tableau_name]

    if topology == "time_multiplexed":

        def field(mm, h):
            return sto.llg_field(mm, params_e, None, h)  # single oscillator

        step = integrators.make_step(field, tableau)

        def per_tick(m_c, tick_in):
            u_t, mask_t = tick_in
            x_prev = m_c[..., 0]  # (E, N) previous tick's snapshots
            h = params_e.a_in * jnp.einsum(
                "ni,ei->en", w_in, u_t, precision=EXACT_MATMUL
            )
            h = h + params_e.a_cp * jnp.einsum(
                "nj,ej->en", w_cp, x_prev, precision=EXACT_MATMUL
            )
            s0 = m_c[:, -1:, :]  # carried oscillator state (E, 1, 3)

            def per_node(s, h_col):  # h_col (E,) — this node's drive
                def inner(si, _):
                    return step(si, dt, h_col[:, None]), None

                s_new, _ = jax.lax.scan(inner, s, None, length=hold_steps)
                return s_new, s_new[:, 0, :]  # snapshot (E, 3)

            sT, snaps = jax.lax.scan(per_node, s0, jnp.transpose(h))
            m_new = jnp.transpose(snaps, (1, 0, 2))  # (E, N, 3)
            m_new = jnp.where(mask_t[:, None, None], m_new, m_c)
            return m_new, jnp.transpose(m_new[..., 0])  # (N, E)

        mT, states = jax.lax.scan(per_tick, m, (u_block, mask_block))
        return jnp.transpose(mT, (2, 1, 0)), states  # (3, N, E), (K, N, E)

    # array_transient
    def field(mm, h):
        return sto.llg_field(mm, params_e, w_cp, h)

    step = integrators.make_step(field, tableau)
    w = int(readout_window)

    def per_tick(m_c, tick_in):
        u_t, mask_t = tick_in
        h_in = params_e.a_in * jnp.einsum(
            "ni,ei->en", w_in, u_t, precision=EXACT_MATMUL
        )  # (E, N)

        def inner(mi, _):
            return step(mi, dt, h_in), None

        m_mid = m_c
        if hold_steps > w:
            m_mid, _ = jax.lax.scan(inner, m_c, None, length=hold_steps - w)

        def tail(mi, _):
            mi2 = step(mi, dt, h_in)
            return mi2, mi2[..., 0]  # (E, N)

        m_new, xs = jax.lax.scan(tail, m_mid, None, length=w)
        state = jnp.mean(xs, axis=0) if w > 1 else xs[0]
        m_new = jnp.where(mask_t[:, None, None], m_new, m_c)
        state = jnp.where(mask_t[:, None], state, m_c[..., 0])
        return m_new, jnp.transpose(state)  # (N, E)

    mT, states = jax.lax.scan(per_tick, m, (u_block, mask_block))
    return jnp.transpose(mT, (2, 1, 0)), states  # (3, N, E), (K, N, E)


@functools.partial(
    jax.jit,
    static_argnames=(
        "topology", "readout_window", "dt", "hold_steps", "impl", "n_inner",
        "block_n", "block_e", "interpret", "precision",
    ),
)
def _tick_chunk_planes_family(
    params_e, w_cp, w_in, m_planes, u_block, mask_block,
    *, topology, readout_window, dt, hold_steps, impl, n_inner, block_n,
    block_e, interpret, precision="highest",
):
    """K-tick family chunk in the kernel (3, N, E) planes layout.

    Every family computes the whole (K, N, E) input-field block with ONE
    GEMM per chunk (`_input_field`, "mixed" reduces it) and casts W once
    (`ops._coupling_operand`, "bf16_coupling"/"mixed" reduce it) — for
    time_multiplexed the W cast lands on the delayed-feedback GEMM, the
    family's one O(N^2) term. impl="ref" and impl="chunk" share one body
    per family (kernels/ref.py), so they are bit-identical by construction;
    array_transient under "fused"/"tiled" splits each hold window through
    the Pallas launchers ((hold - w) fused steps + w single steps).
    """
    e = m_planes.shape[-1]
    pv = kref.pack_params(params_e, e, m_planes.dtype)
    a_in = jnp.reshape(params_e.a_in, (-1,)) * jnp.ones((e,), m_planes.dtype)
    h_block = _input_field(w_in, u_block, a_in, precision)  # (K, N, E)
    w_c = ops._coupling_operand(w_cp, precision)

    if topology == "time_multiplexed":
        return kref.tm_chunk_planes(
            m_planes, w_c, pv, dt, hold_steps, h_block, mask_block
        )

    # array_transient
    if impl in ("ref", "chunk"):
        return kref.rk4_chunk_planes_window(
            m_planes, w_c, pv, dt, hold_steps, readout_window,
            h_block, mask_block,
        )

    w = int(readout_window)
    kw = dict(
        dt=dt, impl=impl, block_n=block_n, block_e=block_e,
        interpret=interpret, precision=precision,
    )

    def per_tick(m_c, tick_in):
        h_t, mask_t = tick_in
        m_mid = m_c
        if hold_steps > w:
            m_mid = ops._integrate_planes_jit(
                m_c, w_cp, pv, h_t, None,
                n_steps=hold_steps - w,
                n_inner=min(n_inner, hold_steps - w), **kw,
            )

        def tail(s, _):
            s2 = ops._integrate_planes_jit(
                s, w_cp, pv, h_t, None, n_steps=1, n_inner=1, **kw
            )
            return s2, s2[0]

        m_new, xs = jax.lax.scan(tail, m_mid, None, length=w)  # xs (w, N, E)
        state = jnp.mean(xs, axis=0) if w > 1 else xs[0]
        m_new = jnp.where(mask_t[None, None, :], m_new, m_c)
        state = jnp.where(mask_t[None, :], state, m_c[0])
        return m_new, state

    mT, states = jax.lax.scan(per_tick, m_planes, (h_block, mask_block))
    return mT, states  # (3, N, E), (K, N, E)


@functools.partial(
    jax.jit,
    static_argnames=(
        "learn", "knob", "topology", "readout_window", "hold_steps",
        "tableau_name",
    ),
)
def _tick_chunk_scan_family_learn(
    params_e, w_cp, w_in, m_planes, u_block, mask_block, y_block,
    lmask_block, p0, w0, dt,
    *, learn, knob, topology, readout_window, hold_steps, tableau_name,
):
    """Family chunk + online readout update, one dispatch (core layout).

    The learn tails are topology-blind — they consume the (K, N, E) states
    block whatever physics produced it — so families inherit both learners
    from the coupled path unchanged (learn="rls": knob=lam; "lms": knob=mu,
    p0=None)."""
    mT, states = _tick_chunk_scan_family(
        params_e, w_cp, w_in, m_planes, u_block, mask_block, dt,
        topology=topology, readout_window=readout_window,
        hold_steps=hold_steps, tableau_name=tableau_name,
    )
    if learn == "lms":
        wT, preds = _lms_chunk_tail(states, y_block, lmask_block, w0, knob)
        return mT, states, wT, preds
    pT, wT, preds = _learn_chunk_tail(states, y_block, lmask_block, p0, w0, knob)
    return mT, states, pT, wT, preds


@functools.partial(
    jax.jit,
    static_argnames=(
        "learn", "knob", "topology", "readout_window", "dt", "hold_steps",
        "impl", "n_inner", "block_n", "block_e", "interpret", "precision",
    ),
)
def _tick_chunk_planes_family_learn(
    params_e, w_cp, w_in, m_planes, u_block, mask_block, y_block,
    lmask_block, p0, w0,
    *, learn, knob, topology, readout_window, dt, hold_steps, impl, n_inner,
    block_n, block_e, interpret, precision="highest",
):
    """Family chunk + online readout update, one dispatch (planes layout).
    As everywhere else, the learn recursion runs in the state dtype —
    reduced precision stops at the readout-learning boundary."""
    mT, states = _tick_chunk_planes_family(
        params_e, w_cp, w_in, m_planes, u_block, mask_block,
        topology=topology, readout_window=readout_window, dt=dt,
        hold_steps=hold_steps, impl=impl, n_inner=n_inner, block_n=block_n,
        block_e=block_e, interpret=interpret, precision=precision,
    )
    if learn == "lms":
        wT, preds = _lms_chunk_tail(states, y_block, lmask_block, w0, knob)
        return mT, states, wT, preds
    pT, wT, preds = _learn_chunk_tail(states, y_block, lmask_block, p0, w0, knob)
    return mT, states, pT, wT, preds


# ---------------------------------------------------------------------------
# CompiledSim
# ---------------------------------------------------------------------------


class CompiledSim:
    """A SimSpec bound to resolved execution decisions. Build via compile_plan."""

    def __init__(self, spec: SimSpec, plan: ExecPlan, impl: str):
        self.spec = spec
        self.plan = plan
        self.impl = impl  # resolved: scan | ref | fused | tiled | chunk
        self.e = plan.ensemble
        self.topology = spec.topology
        self._readout_window = int(spec.readout_window)
        self._block_n = plan.block_n or ops.LANE
        self._block_e = plan.block_e or ops.LANE
        self._n_inner = plan.n_inner or spec.hold_steps
        self._dt_scan = jnp.asarray(spec.dt, spec.dtype)
        # static per-plan: the normalized precision tag the planes workers
        # specialize on ("highest" = bit-exact default) and the resolved
        # sharded gather dtype (precision subsumes the ad-hoc gather_dtype)
        self.precision = ops.normalize_precision(plan.precision)
        self._gather_dtype = plan.effective_gather_dtype
        # static: the learn workers specialize on their knob (RLS: lam == 1
        # skips the per-tick P rescale; LMS: mu is baked into the gain)
        self._lam = float(plan.learn_lam) if plan.learn else None
        self._mu = float(plan.learn_mu) if plan.learn == "lms" else None
        self._params_cache: Optional[STOParams] = None

    def init_learn_state(self) -> Tuple[Optional[jnp.ndarray], jnp.ndarray]:
        """Fresh learn_state lanes for the plan's learner, with S = N + 1
        (states + bias) and n_out = 1.

        learn="rls": (P (E, S, S) = I / learn_reg, W (E, S, 1) = 0).
        learn="lms": (None, W (E, S, 1) = 0) — LMS carries no P block; the
        None slot keeps the (P, W) tuple contract uniform across learners.
        Serving keeps these per-slot (SlotStore); callers driving tick_chunk
        by hand start here. For n_out != 1, call kernels.rls.rls_init /
        lms_init directly."""
        if self.plan.learn is None:
            raise ValueError("init_learn_state() requires ExecPlan(learn=...)")
        if self.plan.learn == "lms":
            return None, krls.lms_init(self.e, self.spec.n + 1, 1, self.spec.dtype)
        return krls.rls_init(
            self.e, self.spec.n + 1, 1, self.plan.learn_reg, self.spec.dtype
        )

    # -- parameter plumbing ------------------------------------------------

    def ensemble_params(self, params: Optional[STOParams] = None) -> STOParams:
        """Per-lane STOParams with (E, 1) leaves (scalar specs broadcast)."""
        if params is None:
            if self._params_cache is None:
                self._params_cache = self._broadcast(self.spec.params)
            return self._params_cache
        return self._broadcast(params)

    def _broadcast(self, p: STOParams) -> STOParams:
        from repro.core.ensemble import broadcast_params

        leaf = jnp.asarray(p.gamma)
        if leaf.ndim == 2 and leaf.shape == (self.e, 1):
            return p
        return broadcast_params(p, self.e)

    def _coerce_batch_u(self, u, keep_shared: bool = False) -> jnp.ndarray:
        """(T, N_in) shared or (T, E, N_in) per lane -> (T, E, N_in).

        keep_shared=True returns a valid shared series un-broadcast — the
        sharded path replicates it across devices instead of storing and
        contracting E per-lane copies.
        """
        spec = self.spec
        u = jnp.asarray(u, dtype=spec.dtype)
        if u.ndim == 2 and u.shape[1] == spec.n_in:
            if keep_shared:
                return u
            return jnp.broadcast_to(u[:, None, :], (u.shape[0], self.e, spec.n_in))
        if u.ndim == 3 and u.shape[1:] == (self.e, spec.n_in):
            return u
        raise ValueError(
            f"batch input series must have shape (T, {spec.n_in}) — shared "
            f"across lanes — or (T, {self.e}, {spec.n_in}) per lane; got "
            f"{tuple(u.shape)}"
        )

    def _coerce_batch_m0(self, m0) -> jnp.ndarray:
        spec = self.spec
        if m0 is None:
            return jnp.broadcast_to(spec.m0, (self.e, spec.n, 3))
        m0 = jnp.asarray(m0, dtype=spec.dtype)
        if m0.shape == (spec.n, 3):
            return jnp.broadcast_to(m0, (self.e, spec.n, 3))
        if m0.shape != (self.e, spec.n, 3):
            raise ValueError(
                f"m0 must have shape ({spec.n}, 3) or ({self.e}, {spec.n}, 3); "
                f"got {tuple(m0.shape)}"
            )
        return m0

    # -- entry points ------------------------------------------------------

    def drive(
        self, u_seq, m0: Optional[jnp.ndarray] = None
    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Solo drive: input series (T, N_in) -> (final m (N, 3), states (T, N)).

        Requires ensemble == 1 and an unsharded plan; impl="scan" is
        bit-identical to the legacy `reservoir.drive`.
        """
        from repro.core.reservoir import coerce_input_series

        spec = self.spec
        if self.e != 1 or self.plan.sharded:
            raise ValueError(
                "drive() is the solo entry point (ensemble == 1, no mesh); "
                "use drive_batch() for ensemble/sharded plans"
            )
        u_seq = coerce_input_series(u_seq, spec.n_in, spec.dtype)
        m_start = spec.m0 if m0 is None else jnp.asarray(m0, dtype=spec.dtype)
        if m_start.shape != spec.m0.shape:
            raise ValueError(
                f"m0 must have shape {tuple(spec.m0.shape)}; got {tuple(m_start.shape)}"
            )
        if self.topology != "coupled_array":
            # families drive through their chunk worker: T ticks, one lane
            mT, states = self._family_chunk_infer(
                self.ensemble_params(), ops.to_planes(m_start),
                u_seq[:, None, :], jnp.ones((u_seq.shape[0], 1), dtype=bool),
            )
            return ops.from_planes(mT, ()), states[:, :, 0]
        if self.impl == "scan":
            # a (1, 1)-leaved ensemble-of-one spec is legal; the solo scan
            # math wants scalar leaves (identical values, broadcast-free)
            params = jax.tree.map(
                lambda x: jnp.reshape(x, ()) if jnp.asarray(x).ndim else x,
                spec.params,
            )
            return _drive_scan(
                params, spec.w_cp, spec.w_in, m_start, u_seq,
                spec.dt, spec.hold_steps, spec.tableau,
            )
        mT, states = _drive_planes(
            self.ensemble_params(), spec.w_cp, spec.w_in,
            ops.to_planes(m_start), u_seq[:, None, :],
            dt=float(spec.dt), hold_steps=spec.hold_steps, impl=self.impl,
            n_inner=self._n_inner, block_n=self._block_n,
            block_e=self._block_e, interpret=self.plan.interpret,
            precision=self.precision,
        )
        return ops.from_planes(mT, ()), states[:, 0, :]

    def drive_batch(
        self,
        u_seq,
        m0: Optional[jnp.ndarray] = None,
        params: Optional[STOParams] = None,
    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Ensemble drive: E lanes, shared (T, N_in) or per-lane
        (T, E, N_in) input -> (mT (E, N, 3), states (T, E, N))."""
        spec = self.spec
        m0_e = self._coerce_batch_m0(m0)
        params_e = self.ensemble_params(params)
        if self.topology != "coupled_array":
            u_e = self._coerce_batch_u(u_seq)
            mT, states = self._family_chunk_infer(
                params_e, ops.to_planes(m0_e), u_e,
                jnp.ones((u_e.shape[0], self.e), dtype=bool),
            )
            return ops.from_planes(mT, (self.e,)), jnp.transpose(states, (0, 2, 1))
        if self.plan.sharded:
            # a shared series stays (T, N_in): replicated on every device,
            # contracted once per sample ('ni,i->n') instead of per lane
            u_sh = self._coerce_batch_u(u_seq, keep_shared=True)
            return _sharded.drive_sharded(
                self.plan.mesh, params_e, spec.w_cp, spec.w_in, m0_e, u_sh,
                spec.dt, spec.hold_steps,
                ensemble_axes=self.plan.ensemble_axes,
                model_axis=self.plan.model_axis,
                tableau_name=spec.tableau,
                gather_dtype=self._gather_dtype,
                precision=self.precision,
            )
        u_e = self._coerce_batch_u(u_seq)
        if self.impl == "scan":
            return _drive_scan_batch(
                params_e, spec.w_cp, spec.w_in, m0_e, u_e,
                spec.dt, spec.hold_steps, spec.tableau,
            )
        mT, states = _drive_planes(
            params_e, spec.w_cp, spec.w_in, ops.to_planes(m0_e), u_e,
            dt=float(spec.dt), hold_steps=spec.hold_steps, impl=self.impl,
            n_inner=self._n_inner, block_n=self._block_n,
            block_e=self._block_e, interpret=self.plan.interpret,
            precision=self.precision,
        )
        return ops.from_planes(mT, (self.e,)), states

    def integrate(
        self,
        n_steps: int,
        m0: Optional[jnp.ndarray] = None,
        save_every: int = 0,
        params: Optional[STOParams] = None,
    ):
        """Free-run (u = 0) integration of the E-lane ensemble.

        Returns (mT (E, N, 3), traj or None) — traj has shape
        (n_steps // save_every, E, N, 3) when save_every > 0. impl="scan"
        reproduces the legacy `ensemble.integrate_ensemble` exactly.
        """
        spec = self.spec
        if self.topology == "time_multiplexed":
            raise ValueError(
                "integrate() free-runs the coupled array; a time_multiplexed "
                "reservoir has no input-free virtual-node evolution — drive "
                "it with a zero input series instead"
            )
        # array_transient falls through: its free-run dynamics ARE the
        # coupled array's (the readout window only shapes emitted states)
        m0_e = self._coerce_batch_m0(m0)
        params_e = self.ensemble_params(params)
        if self.plan.sharded:
            if save_every:
                raise NotImplementedError("save_every on sharded plans")
            return (
                _sharded.integrate_sharded(
                    self.plan.mesh, params_e, spec.w_cp, m0_e, spec.dt, n_steps,
                    ensemble_axes=self.plan.ensemble_axes,
                    model_axis=self.plan.model_axis,
                    tableau_name=spec.tableau,
                    gather_dtype=self._gather_dtype,
                    precision=self.precision,
                ),
                None,
            )
        if self.impl == "scan":
            # unjitted like the legacy integrate_ensemble (lax.scan compiles
            # the trajectory either way; op-for-op identical results)
            tableau = integrators.TABLEAUX[spec.tableau]

            def field(m, _):
                return sto.llg_field(m, params_e, spec.w_cp)

            return integrators.integrate_scan(
                field, m0_e, spec.dt, n_steps, None, tableau, save_every=save_every
            )
        if save_every:
            assert n_steps % save_every == 0
        mT, traj = _integrate_planes(
            params_e, spec.w_cp, ops.to_planes(m0_e),
            dt=float(spec.dt), n_steps=n_steps, save_every=save_every,
            impl=self.impl, n_inner=self._n_inner, block_n=self._block_n,
            block_e=self._block_e, interpret=self.plan.interpret,
            precision=self.precision,
        )
        mT = ops.from_planes(mT, (self.e,))
        if traj is not None:
            traj = jax.vmap(lambda mp: ops.from_planes(mp, (self.e,)))(traj)
        return mT, traj

    def tick(
        self,
        m_planes: jnp.ndarray,  # (3, N, E) slot-store layout
        u: jnp.ndarray,  # (E, N_in) this tick's input row per lane
        lane_mask: Optional[jnp.ndarray] = None,  # (E,) bool; None = all active
        params: Optional[STOParams] = None,  # per-lane STOParams, (E, 1) leaves
    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """ONE hold window for a slot batch — the serving hot path.

        Returns (m_planes' (3, N, E), states plane (N, E)). Lanes where
        lane_mask is False come back bit-identical (idle serving slots stay
        frozen while active slots advance in the same dispatch).
        """
        spec = self.spec
        params_e = self.ensemble_params(params)
        if lane_mask is None:
            lane_mask = jnp.ones((self.e,), dtype=bool)
        if self.topology != "coupled_array":
            # a tick is a K=1 chunk: one body per family keeps serving's
            # per-tick and chunked paths bit-identical by construction
            mT, states = self._family_chunk_infer(
                params_e, m_planes, u[None], jnp.asarray(lane_mask, bool)[None]
            )
            return mT, states[0]
        if self.plan.sharded:
            m = jnp.transpose(m_planes, (2, 1, 0))  # (E, N, 3)
            m_new, states = _sharded.tick_sharded(
                self.plan.mesh, params_e, spec.w_cp, spec.w_in, m, u, lane_mask,
                spec.dt, spec.hold_steps,
                ensemble_axes=self.plan.ensemble_axes,
                model_axis=self.plan.model_axis,
                tableau_name=spec.tableau,
                gather_dtype=self._gather_dtype,
                precision=self.precision,
            )
            return jnp.transpose(m_new, (2, 1, 0)), jnp.transpose(states)
        if self.impl == "scan":
            return _tick_scan(
                params_e, spec.w_cp, spec.w_in, m_planes, u, lane_mask,
                self._dt_scan, spec.hold_steps, spec.tableau,
            )
        return _tick_planes(
            params_e, spec.w_cp, spec.w_in, m_planes, u, lane_mask,
            dt=float(spec.dt), hold_steps=spec.hold_steps, impl=self.impl,
            n_inner=self._n_inner, block_n=self._block_n,
            block_e=self._block_e, interpret=self.plan.interpret,
            precision=self.precision,
        )

    def _coerce_tick_mask(self, lane_mask, k: int) -> jnp.ndarray:
        """(E,) or (K, E) bool -> (K, E) mask block (None = all active)."""
        if lane_mask is None:
            return jnp.ones((k, self.e), dtype=bool)
        lane_mask = jnp.asarray(lane_mask, dtype=bool)
        if lane_mask.shape == (self.e,):
            return jnp.broadcast_to(lane_mask[None, :], (k, self.e))
        if lane_mask.shape == (k, self.e):
            return lane_mask
        raise ValueError(
            f"lane_mask must have shape ({k}, {self.e}) or ({self.e},); "
            f"got {tuple(lane_mask.shape)}"
        )

    def tick_chunk(
        self,
        m_planes: jnp.ndarray,  # (3, N, E) slot-store layout
        u_block: jnp.ndarray,  # (K, E, N_in) input rows for K ticks
        lane_mask: Optional[jnp.ndarray] = None,  # (K, E) or (E,) bool
        params: Optional[STOParams] = None,  # per-lane STOParams, (E, 1) leaves
        targets: Optional[jnp.ndarray] = None,  # (K, E, n_out) learn targets
        learn_state: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,  # (P, W)
        learn_mask: Optional[jnp.ndarray] = None,  # (K, E) or (E,) bool
    ):
        """K serving ticks (K hold windows) for a slot batch in ONE dispatch.

        The chunked serving hot path (`ExecPlan.chunk_ticks`): a lax.scan
        over the K input ticks keeps every intermediate states plane in a
        device-side buffer, so the host pays one transfer per chunk instead
        of one per tick. Returns (m_planes' (3, N, E), states (K, N, E)).

        lane_mask may be per tick (K, E) — a lane masked False for rows
        [0, k) and True after integrates exactly as if admitted at tick k
        (frozen lanes are bit-identical), and the mirror image retires a
        lane mid-chunk; or a single (E,) row applied to every tick. On the
        scan impl a K-chunk is bit-identical to K sequential `tick` calls
        (pinned by tests/test_serve_chunked.py); the planes impls and
        sharded plans agree within the kernel suite's tolerance.

        With `ExecPlan(learn="rls")` the chunk also LEARNS: pass
        `learn_state=(P (E, S, S), W (E, S, n_out))` (see
        `init_learn_state`) and `targets` (K, E, n_out), and every tick
        applies one masked batched RLS update (kernels/rls.py) to the learn
        lanes inside the same scan — no extra dispatches or host
        round-trips. `learn_mask` (default: lane_mask) gates which lanes
        learn which ticks; masked ticks leave P/W bit-identical, so
        washout, idle slots, and inference-only tenants all ride the same
        dispatch. Returns
        (m', states, (P', W'), preds (K, E, n_out)) — preds are the
        a-priori (pre-update) predictions. The integration itself is
        unchanged: m' and states are bit-identical to the inference-only
        chunk on every impl.
        """
        spec = self.spec
        params_e = self.ensemble_params(params)
        u_block = jnp.asarray(u_block, spec.dtype)
        if u_block.ndim != 3 or u_block.shape[1:] != (self.e, spec.n_in):
            raise ValueError(
                f"u_block must have shape (K, {self.e}, {spec.n_in}); "
                f"got {tuple(u_block.shape)}"
            )
        k = u_block.shape[0]
        mask_block = self._coerce_tick_mask(lane_mask, k)
        if self.plan.learn is None:
            if targets is not None or learn_state is not None or learn_mask is not None:
                raise ValueError(
                    "targets/learn_state/learn_mask require an "
                    "ExecPlan(learn='rls') plan; this plan is inference-only"
                )
            return self._tick_chunk_infer(params_e, m_planes, u_block, mask_block)
        if learn_state is None or targets is None:
            raise ValueError(
                f"ExecPlan(learn={self.plan.learn!r}) tick_chunk needs "
                "learn_state=(P, W) (P is None for learn='lms') and targets "
                "(K, E, n_out); for an inference-only chunk compile a plan "
                "with learn=None"
            )
        p0, w0 = learn_state
        n_out = w0.shape[-1]
        targets = jnp.asarray(targets, spec.dtype)
        if targets.shape != (k, self.e, n_out):
            raise ValueError(
                f"targets must have shape ({k}, {self.e}, {n_out}) to match "
                f"the u block and learn_state W lanes; got {tuple(targets.shape)}"
            )
        if w0.shape[:2] != (self.e, spec.n + 1):
            raise ValueError(
                f"learn_state W must have shape ({self.e}, {spec.n + 1}, "
                f"n_out); got {tuple(w0.shape)}"
            )
        lmask_block = (
            mask_block if learn_mask is None else self._coerce_tick_mask(learn_mask, k)
        )
        if self.topology != "coupled_array":
            return self._family_chunk_learn(
                params_e, m_planes, u_block, mask_block, targets, lmask_block,
                p0, w0,
            )
        if self.plan.learn == "lms":
            if p0 is not None:
                raise ValueError(
                    "learn='lms' carries no P block; pass learn_state="
                    "(None, W) (see init_learn_state)"
                )
            if self.impl == "scan":
                mT, states, wT, preds = _tick_chunk_scan_lms(
                    params_e, spec.w_cp, spec.w_in, m_planes, u_block,
                    mask_block, targets, lmask_block, w0, self._mu,
                    self._dt_scan, spec.hold_steps, spec.tableau,
                )
            else:
                mT, states, wT, preds = _tick_chunk_planes_lms(
                    params_e, spec.w_cp, spec.w_in, m_planes, u_block,
                    mask_block, targets, lmask_block, w0, mu=self._mu,
                    dt=float(spec.dt), hold_steps=spec.hold_steps,
                    impl=self.impl, n_inner=self._n_inner,
                    block_n=self._block_n, block_e=self._block_e,
                    interpret=self.plan.interpret, precision=self.precision,
                )
            return mT, states, (None, wT), preds
        if p0 is None or p0.shape != (self.e, spec.n + 1, spec.n + 1):
            raise ValueError(
                f"learn_state must be (P ({self.e}, {spec.n + 1}, "
                f"{spec.n + 1}), W ({self.e}, {spec.n + 1}, n_out)); got "
                f"P={None if p0 is None else tuple(p0.shape)}"
            )
        if self.plan.sharded:
            m = jnp.transpose(m_planes, (2, 1, 0))  # (E, N, 3)
            m_new, states, pT, wT, preds = _sharded.tick_chunk_sharded_rls(
                self.plan.mesh, params_e, spec.w_cp, spec.w_in, m,
                u_block, mask_block, targets, lmask_block, p0, w0,
                self._lam, spec.dt, spec.hold_steps,
                ensemble_axes=self.plan.ensemble_axes,
                model_axis=self.plan.model_axis,
                tableau_name=spec.tableau,
                gather_dtype=self._gather_dtype,
                precision=self.precision,
            )
            # states arrive (K, E, N): shuffle to the (K, N, E) block contract
            return (
                jnp.transpose(m_new, (2, 1, 0)),
                jnp.transpose(states, (0, 2, 1)),
                (pT, wT),
                preds,
            )
        if self.impl == "scan":
            mT, states, pT, wT, preds = _tick_chunk_scan_rls(
                params_e, spec.w_cp, spec.w_in, m_planes, u_block, mask_block,
                targets, lmask_block, p0, w0, self._lam,
                self._dt_scan, spec.hold_steps, spec.tableau,
            )
            return mT, states, (pT, wT), preds
        mT, states, pT, wT, preds = _tick_chunk_planes_rls(
            params_e, spec.w_cp, spec.w_in, m_planes, u_block, mask_block,
            targets, lmask_block, p0, w0, lam=self._lam,
            dt=float(spec.dt), hold_steps=spec.hold_steps, impl=self.impl,
            n_inner=self._n_inner, block_n=self._block_n,
            block_e=self._block_e, interpret=self.plan.interpret,
            precision=self.precision,
        )
        return mT, states, (pT, wT), preds

    def _family_chunk_infer(
        self, params_e, m_planes, u_block, mask_block
    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Inference chunk for the non-coupled families (compile_plan keeps
        mesh plans out of here — families are unsharded by validation)."""
        spec = self.spec
        if self.impl == "scan":
            return _tick_chunk_scan_family(
                params_e, spec.w_cp, spec.w_in, m_planes, u_block, mask_block,
                self._dt_scan, topology=self.topology,
                readout_window=self._readout_window,
                hold_steps=spec.hold_steps, tableau_name=spec.tableau,
            )
        return _tick_chunk_planes_family(
            params_e, spec.w_cp, spec.w_in, m_planes, u_block, mask_block,
            topology=self.topology, readout_window=self._readout_window,
            dt=float(spec.dt), hold_steps=spec.hold_steps, impl=self.impl,
            n_inner=self._n_inner, block_n=self._block_n,
            block_e=self._block_e, interpret=self.plan.interpret,
            precision=self.precision,
        )

    def _family_chunk_learn(
        self, params_e, m_planes, u_block, mask_block, targets, lmask_block,
        p0, w0,
    ):
        """Learning chunk for the non-coupled families (same (P, W)/preds
        contract as the coupled learn paths)."""
        spec = self.spec
        learn = self.plan.learn
        if learn == "lms":
            if p0 is not None:
                raise ValueError(
                    "learn='lms' carries no P block; pass learn_state="
                    "(None, W) (see init_learn_state)"
                )
            knob = self._mu
        else:
            if p0 is None or p0.shape != (self.e, spec.n + 1, spec.n + 1):
                raise ValueError(
                    f"learn_state must be (P ({self.e}, {spec.n + 1}, "
                    f"{spec.n + 1}), W ({self.e}, {spec.n + 1}, n_out)); got "
                    f"P={None if p0 is None else tuple(p0.shape)}"
                )
            knob = self._lam
        if self.impl == "scan":
            out = _tick_chunk_scan_family_learn(
                params_e, spec.w_cp, spec.w_in, m_planes, u_block, mask_block,
                targets, lmask_block, p0, w0, self._dt_scan,
                learn=learn, knob=knob, topology=self.topology,
                readout_window=self._readout_window,
                hold_steps=spec.hold_steps, tableau_name=spec.tableau,
            )
        else:
            out = _tick_chunk_planes_family_learn(
                params_e, spec.w_cp, spec.w_in, m_planes, u_block, mask_block,
                targets, lmask_block, p0, w0,
                learn=learn, knob=knob, topology=self.topology,
                readout_window=self._readout_window, dt=float(spec.dt),
                hold_steps=spec.hold_steps, impl=self.impl,
                n_inner=self._n_inner, block_n=self._block_n,
                block_e=self._block_e, interpret=self.plan.interpret,
                precision=self.precision,
            )
        if learn == "lms":
            mT, states, wT, preds = out
            return mT, states, (None, wT), preds
        mT, states, pT, wT, preds = out
        return mT, states, (pT, wT), preds

    def _tick_chunk_infer(
        self, params_e, m_planes, u_block, mask_block
    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Inference-only chunk body (plan.learn is None)."""
        spec = self.spec
        if self.topology != "coupled_array":
            return self._family_chunk_infer(params_e, m_planes, u_block, mask_block)
        if self.plan.sharded:
            m = jnp.transpose(m_planes, (2, 1, 0))  # (E, N, 3)
            m_new, states = _sharded.tick_chunk_sharded(
                self.plan.mesh, params_e, spec.w_cp, spec.w_in, m,
                u_block, mask_block, spec.dt, spec.hold_steps,
                ensemble_axes=self.plan.ensemble_axes,
                model_axis=self.plan.model_axis,
                tableau_name=spec.tableau,
                gather_dtype=self._gather_dtype,
                precision=self.precision,
            )
            # states arrive (K, E, N): shuffle to the (K, N, E) block contract
            return jnp.transpose(m_new, (2, 1, 0)), jnp.transpose(states, (0, 2, 1))
        if self.impl == "scan":
            return _tick_chunk_scan(
                params_e, spec.w_cp, spec.w_in, m_planes, u_block, mask_block,
                self._dt_scan, spec.hold_steps, spec.tableau,
            )
        return _tick_chunk_planes(
            params_e, spec.w_cp, spec.w_in, m_planes, u_block, mask_block,
            dt=float(spec.dt), hold_steps=spec.hold_steps, impl=self.impl,
            n_inner=self._n_inner, block_n=self._block_n,
            block_e=self._block_e, interpret=self.plan.interpret,
            precision=self.precision,
        )

    # -- warm-up / AOT -----------------------------------------------------

    def _warmup_inputs(self, n_out: int):
        """Representative zero-valued tick_chunk inputs: shapes and dtypes
        match what the serving loop dispatches (mask values never change
        the executable), so compiling on these warms the real hot path."""
        spec = self.spec
        k = max(self.plan.chunk_ticks, 1)
        m = ops.to_planes(jnp.broadcast_to(spec.m0, (self.e, spec.n, 3)))
        u = jnp.zeros((k, self.e, spec.n_in), spec.dtype)
        mask = jnp.zeros((k, self.e), dtype=bool)
        if self.plan.learn is None:
            return m, u, mask, None, None
        s = spec.n + 1
        if self.plan.learn == "lms":
            state = (None, krls.lms_init(self.e, s, n_out, spec.dtype))
        else:
            state = krls.rls_init(
                self.e, s, n_out, self.plan.learn_reg, spec.dtype
            )
        targets = jnp.zeros((k, self.e, n_out), spec.dtype)
        return m, u, mask, targets, state

    def warmup(self, n_out: int = 1) -> "CompiledSim":
        """Force XLA compilation of the chunked serving hot path by
        executing ONE all-lanes-masked zero chunk (per-FLOP cost of a
        single chunk; masked lanes make it state-neutral by construction).

        Unlike `aot_compile`, this populates the in-process jit fast path
        for the exact executable `tick_chunk` dispatches — an engine that
        rescales into a warmed bucket pays zero XLA work at the chunk
        boundary. Learn plans specialize on n_out (the readout width is a
        trace shape); pass the serving n_out to warm that variant.
        """
        m, u, mask, targets, state = self._warmup_inputs(n_out)
        if targets is None:
            out = self.tick_chunk(m, u, lane_mask=mask)
        else:
            out = self.tick_chunk(
                m, u, lane_mask=mask, targets=targets,
                learn_state=state, learn_mask=mask,
            )
        jax.block_until_ready(out[0])
        return self

    def _chunk_worker_call(self, n_out: int = 1):
        """(jitted worker, args, kwargs) for the exact module-level call
        tick_chunk dispatches — the AOT lowering target."""
        if self.plan.sharded:
            raise NotImplementedError(
                "AOT lowering covers unsharded plans; sharded plans warm by "
                "executing one masked chunk (CompiledSim.warmup)"
            )
        if self.topology != "coupled_array":
            raise NotImplementedError(
                "AOT lowering covers coupled_array plans; family plans warm "
                "by executing one masked chunk (CompiledSim.warmup)"
            )
        spec = self.spec
        params_e = self.ensemble_params()
        m, u, mask, targets, state = self._warmup_inputs(n_out)
        planes_kw = dict(
            dt=float(spec.dt), hold_steps=spec.hold_steps, impl=self.impl,
            n_inner=self._n_inner, block_n=self._block_n,
            block_e=self._block_e, interpret=self.plan.interpret,
            precision=self.precision,
        )
        if self.plan.learn is None:
            if self.impl == "scan":
                return _tick_chunk_scan, (
                    params_e, spec.w_cp, spec.w_in, m, u, mask,
                    self._dt_scan, spec.hold_steps, spec.tableau,
                ), {}
            return _tick_chunk_planes, (
                params_e, spec.w_cp, spec.w_in, m, u, mask,
            ), planes_kw
        p0, w0 = state
        if self.plan.learn == "lms":
            if self.impl == "scan":
                return _tick_chunk_scan_lms, (
                    params_e, spec.w_cp, spec.w_in, m, u, mask, targets,
                    mask, w0, self._mu, self._dt_scan, spec.hold_steps,
                    spec.tableau,
                ), {}
            return _tick_chunk_planes_lms, (
                params_e, spec.w_cp, spec.w_in, m, u, mask, targets,
                mask, w0,
            ), dict(mu=self._mu, **planes_kw)
        if self.impl == "scan":
            return _tick_chunk_scan_rls, (
                params_e, spec.w_cp, spec.w_in, m, u, mask, targets,
                mask, p0, w0, self._lam, self._dt_scan, spec.hold_steps,
                spec.tableau,
            ), {}
        return _tick_chunk_planes_rls, (
            params_e, spec.w_cp, spec.w_in, m, u, mask, targets,
            mask, p0, w0,
        ), dict(lam=self._lam, **planes_kw)

    def lower_tick_chunk(self, n_out: int = 1):
        """AOT-lower the chunked hot path (a `jax.stages.Lowered`).

        Raises NotImplementedError for sharded plans (use `warmup`)."""
        fn, args, kwargs = self._chunk_worker_call(n_out)
        return fn.lower(*args, **kwargs)

    def aot_compile(self, n_out: int = 1) -> "CompiledSim":
        """`lower().compile()` the chunked hot path without executing it.

        Zero FLOPs: the XLA compile happens now (and lands in the
        persistent compilation cache when one is configured — see
        `ExecPlan.compilation_cache_dir`) instead of at first dispatch.
        The in-process jit fast path still keys its own first call, so
        serving loops that must never stall use `warmup` instead; AOT is
        the restart-survival and compile-time-measurement path.
        """
        self.lower_tick_chunk(n_out).compile()
        return self


# ---------------------------------------------------------------------------
# compile_plan
# ---------------------------------------------------------------------------


def _kernel_shape(spec: SimSpec, plan: ExecPlan) -> dict:
    """The plan's kernel-shaping knobs, as the VMEM fit check takes them."""
    return dict(
        k_ticks=max(plan.chunk_ticks, 1), hold_steps=spec.hold_steps,
        n_inner=plan.n_inner or spec.hold_steps,
        block_n=plan.block_n or ops.LANE, block_e=plan.block_e or ops.LANE,
    )


def _check_kernel_fits(spec: SimSpec, plan: ExecPlan, impl: str) -> None:
    """Refuse, here, a Pallas impl the TPU compiler would refuse for VMEM.

    Checks every kernel variant the plan dispatches: the chunk kernel runs
    K ticks in tick_chunk and one in the per-window entry points; the
    array_transient family also steps the fused kernel one step at a time.
    The family chunk bodies are XLA, not the Pallas chunk kernel.
    """
    shape = _kernel_shape(spec, plan)
    k_ticks = shape.pop("k_ticks")
    hold, n_inner = shape.pop("hold_steps"), shape.pop("n_inner")
    if impl == "chunk":
        if spec.topology != "coupled_array":
            return
        variants = {(k_ticks, hold), (1, hold)}
    else:
        variants = {(1, ops.effective_n_inner(hold, n_inner))}
        if spec.topology == "array_transient":
            variants.add((1, 1))
    for k, inner in sorted(variants):
        refusal = ops.kernel_vmem_refusal(
            impl, spec.n, plan.ensemble, itemsize=spec.dtype.itemsize,
            precision=plan.effective_precision, k_ticks=k, n_inner=inner,
            **shape,
        )
        if refusal is not None:
            raise ValueError(
                f"impl={impl!r} cannot hold N={spec.n}, E={plan.ensemble} "
                f"(chunk_ticks={k}, inner steps={inner}, precision="
                f"{ops.normalize_precision(plan.precision)!r}) in the TPU's "
                f"scoped VMEM limit of {ops.sto_step.VMEM_LIMIT_BYTES} bytes; "
                f"use impl='auto' or another impl. Compiler: {refusal}"
            )


def compile_plan(spec: SimSpec, plan: Optional[ExecPlan] = None, **overrides) -> CompiledSim:
    """Bind a SimSpec to an ExecPlan, resolving every execution decision.

    Keyword overrides build/amend the plan: `compile_plan(spec, ensemble=64)`
    == `compile_plan(spec, ExecPlan(ensemble=64))`. "auto" impls resolve
    against the measured-latency dispatch table (persisted per-platform JSON
    included); `measure=True` times the candidates for this (N, E) first and
    pins the winner, so the choice survives into the committed table via
    `kernels.dispatch_table.save_table()`.
    """
    if plan is None:
        plan = ExecPlan(**overrides)
    elif overrides:
        plan = dataclasses.replace(plan, **overrides)

    if plan.compilation_cache_dir:
        from repro.api import cache as _cache  # deferred: cache imports us

        _cache.enable_persistent_cache(plan.compilation_cache_dir)

    if spec.tableau not in integrators.TABLEAUX:
        raise ValueError(
            f"unknown tableau {spec.tableau!r}; choose from {sorted(integrators.TABLEAUX)}"
        )

    # physics-family validation: the spec's family invariants, then the
    # plan/family pairing (api/plan.FAMILY_IMPLS — e.g. the coupled-array
    # Pallas kernels cannot express the time-multiplexed delay line)
    from repro.api.spec import validate_topology

    validate_topology(spec)
    check_plan_supports_topology(plan, spec.topology)

    # fail here, with the fix spelled out, instead of deep inside a scan
    # trace: ensemble-leaved params must match the plan's width
    leaf = jnp.asarray(spec.params.gamma)
    if leaf.ndim == 2 and leaf.shape != (plan.ensemble, 1):
        raise ValueError(
            f"spec.params carries ensemble leaves of shape {tuple(leaf.shape)} "
            f"but the plan runs ensemble={plan.ensemble}; rebuild the sweep "
            f"with broadcast_params(base, {plan.ensemble}) or set "
            f"ExecPlan(ensemble={int(leaf.shape[0])})"
        )
    if leaf.ndim not in (0, 2):
        raise ValueError(
            f"spec.params leaves must be scalars or (E, 1) ensemble leaves "
            f"(broadcast_params); got shape {tuple(leaf.shape)}"
        )

    if plan.sharded:
        impl = "scan"  # sharded plans integrate in the core layout via shard_map
    else:
        impl = plan.impl
        if impl == "auto":
            # choose_impl lazily loads the persisted per-platform table;
            # both the measurement and the lookup are precision-keyed (the
            # impl ranking shifts when the coupling GEMM goes bf16)
            if plan.measure:
                # memoized through the process-wide PlanCache: identical
                # (platform, N, E, dtype, precision, K) keys are timed once
                from repro.api import cache as _cache

                _cache.PLAN_CACHE.measure(
                    spec.n, plan.ensemble, dt=float(spec.dt),
                    dtype=spec.dtype, precision=plan.effective_precision,
                    chunk_ticks=max(plan.chunk_ticks, 1),
                )
            impl = ops.choose_impl(
                spec.n, plan.ensemble, spec.dtype.itemsize,
                precision=plan.effective_precision,
                **_kernel_shape(spec, plan),
            )
            if impl in ("fused", "tiled", "chunk") and spec.tableau != "rk4":
                # the table's winner was measured on RK4 workloads; an
                # auto plan with another tableau falls back to the oracle
                # instead of erroring on a choice the user never made
                impl = "ref"
            if (
                spec.topology == "time_multiplexed"
                and impl in ("fused", "tiled")
            ):
                # the table's winner was measured on the coupled array;
                # fall back rather than error on an auto-made choice
                impl = "ref"
    if impl in ("fused", "tiled", "chunk") and spec.tableau != "rk4":
        raise ValueError(
            f"the fused kernels integrate classical RK4 only; impl={impl!r} "
            f"cannot run tableau {spec.tableau!r} (use impl='scan' or 'ref')"
        )
    if impl in ("fused", "tiled", "chunk") and not plan.interpret:
        _check_kernel_fits(spec, plan, impl)
    sim = CompiledSim(spec, plan, impl)
    if plan.aot:
        try:
            sim.aot_compile()
        except NotImplementedError:
            sim.warmup()
    return sim
