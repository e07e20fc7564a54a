"""Batched online readout updates: recursive least squares (RLS) and
normalized least mean squares (LMS).

The device-side learning rule behind `ExecPlan.learn="rls"`: every serving
tick, each ensemble lane e refines its readout weights W[e] against that
tick's target using the classic RLS recursion

    k   = P x / (lam + x^T P x)          gain        (E, S)
    e   = y - W^T x                      a-priori error
    W'  = W + k e^T                      weight update
    P'  = (P - k (P x)^T) / lam          inverse-Gram update

with x the (S,) = (N + 1,) feature vector (node states + bias), lam the
forgetting factor, and P initialized to I / reg. With lam == 1 the
recursion converges to exactly the regularized normal equations batch ridge
solves: after T updates W equals `fit_ridge(states, targets, reg=reg)` up
to float roundoff, so the streaming path has an offline oracle
(`core.reservoir.fit_rls`) it can be pinned against bit-for-bit.

Everything here is plain jnp on (E, ...)-batched operands, so the SAME
update fuses into every tick_chunk backend: the core-layout scan, the
planes-layout ref/fused/tiled paths (the integrate may be a Pallas kernel;
the update is an einsum around it), and the shard_map'd sharded path (P/W
ride lane-sharded, the feature vector is all-gathered like the coupling
field). The P' expression uses the k (P x)^T outer product — not k (x^T P)
— so P stays symmetric by construction instead of drifting.

Per-lane cost is O(S^2) per tick against the integrate's O(N^2 hold_steps),
so learning rides along at a bounded overhead (benchmarked as the learn-on
column of BENCH_serve.json).

Numerical note: the recursion runs in the reservoir's dtype (f32 for
serving). With lam == 1, P shrinks monotonically and f32 is stable for any
stream length. With aggressive forgetting (lam well below 1) over very
long streams, P's conditioning degrades in f32 — the classic RLS
round-off divergence — so keep lam close to 1 for long-lived f32 sessions
(e.g. 0.99+) or run the spec in float64.

Precision policies (ExecPlan.precision) stop HERE: reduced-precision plans
cast the coupling/input GEMMs of the *integration*, but the learn
recursion always runs in P's dtype — P's conditioning is the one place
bf16 noise compounds tick over tick instead of averaging out, and the
bit-match contract with the offline `fit_rls` oracle only holds if the
update math is unpolluted. Both update entry points upcast reduced-dtype
feature vectors to P's dtype defensively.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.constants import EXACT_MATMUL


def rls_init(
    e: int, n_state: int, n_out: int, reg: float, dtype
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fresh per-lane learning state: P = I / reg, W = 0.

    Returns (P (E, S, S), W (E, S, n_out)). reg plays exactly the role of
    ridge regression's `reg`: an RLS pass with forgetting factor 1 over T
    samples solves (X^T X + reg I) W = X^T Y.
    """
    if reg <= 0:
        raise ValueError(f"reg must be > 0 (P0 = I / reg); got {reg}")
    p0 = jnp.broadcast_to(
        (jnp.eye(n_state, dtype=dtype) / jnp.asarray(reg, dtype))[None],
        (e, n_state, n_state),
    )
    w0 = jnp.zeros((e, n_state, n_out), dtype)
    return p0, w0


def rls_update(
    p: jnp.ndarray,  # (E, S, S) inverse-Gram per lane
    w: jnp.ndarray,  # (E, S, n_out) readout weights per lane
    x: jnp.ndarray,  # (E, S) this tick's feature vector per lane
    y: jnp.ndarray,  # (E, n_out) this tick's target per lane
    mask: jnp.ndarray,  # (E,) bool; False lanes return (p, w) value-frozen
    lam: float,  # STATIC forgetting factor in (0, 1] (a Python float)
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One masked batched RLS step -> (P', W', a-priori predictions (E, n_out)).

    The prediction is computed with the INCOMING weights (before the
    update), i.e. what the lane would have answered for this tick — the
    honest online-learning error signal. Masked-off lanes (idle slots,
    washout ticks, inference-only tenants) keep P and W value-frozen
    (== their previous values; a -0.0 may normalize to +0.0); their
    prediction is still returned (frozen weights applied to the tick's
    states).

    lam is a static Python float, not a traced scalar: the update is fused
    into serving's per-tick scan where every (E, S, S) traversal is billed
    per tick, and the common lam == 1.0 case skips the P rescale entirely.
    Masking folds into the gain (k = 0 -> P - 0, W + 0) rather than a
    jnp.where select over the (E, S, S) P block — two fewer full-P
    traversals per tick, value-identical results.
    """
    # learn math never runs reduced: see the module precision note
    x = x.astype(p.dtype)
    y = y.astype(p.dtype)
    # broadcast-multiply + sum, NOT einsum/dot_general: XLA lowers batched
    # dots with a batch-width-dependent reduction order, while a trailing-
    # axis reduce is bit-identical per lane at any E — that is what lets a
    # served lane bit-match the E=1 offline oracle (core.reservoir.fit_rls)
    px = jnp.sum(p * x[:, None, :], axis=-1)  # (E, S)
    denom = lam + jnp.sum(x * px, axis=-1)  # (E,)
    k = jnp.where(mask[:, None], px / denom[:, None], 0.0)  # (E, S)
    pred = jnp.sum(w * x[:, :, None], axis=1)  # (E, n_out)
    err = y - pred
    w_new = w + k[:, :, None] * err[:, None, :]
    # k (P x)^T, not k (x^T P): symmetric-by-construction P update
    p_new = p - k[:, :, None] * px[:, None, :]
    if lam != 1.0:
        # frozen lanes divide by exactly 1.0 (an IEEE no-op: x / 1.0 == x)
        lam_e = jnp.where(mask, jnp.asarray(lam, p.dtype), p.dtype.type(1.0))
        p_new = p_new / lam_e[:, None, None]
    return p_new, w_new, pred


def rls_chunk(
    p: jnp.ndarray,  # (E, S, S) inverse-Gram per lane
    w: jnp.ndarray,  # (E, S, n_out) readout weights per lane
    xb: jnp.ndarray,  # (K, E, S) feature vectors, one row per tick
    y: jnp.ndarray,  # (K, E, n_out) targets per tick
    mask: jnp.ndarray,  # (K, E) bool; False ticks leave (p, w) value-frozen
    lam: float,  # STATIC forgetting factor in (0, 1]
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """K sequential RLS steps applied with O(1) full-P passes per CHUNK.

    P is the memory giant of RLS — (E, S, S) floats — and the serving chunk
    is K ticks, so the naive per-tick recursion pays ~3K full-P traversals
    per chunk and is memory-bound well past the learn-overhead budget at
    large N. This routine computes the SAME per-tick gain sequence from
    rank-1 algebra on small (E, S) vectors:

        B        = P x_t for all K ticks      ... ONE read of P
        px_t     = cum_t B_t - sum_{j<t} coef_j (px_j . x_t) k_j
        k_t      = mask_t ? px_t / (lam + x_t . px_t) : 0
        W_{t+1}  = W_t + k_t (y_t - W_t^T x_t)^T     (a-priori preds kept)
        P'       = cum_K P - sum_t coef_t k_t px_t^T ... one read + write

    i.e. ~3 full-P traversals per chunk instead of ~3K. The gains are
    mathematically identical to K applications of `rls_update` (exact
    rank-1 expansion of the recursion, with the forgetting/mask factors
    tracked in per-lane scalars); float op order differs, so the offline
    oracle (`core.reservoir.fit_rls(block=K)`) uses THIS routine with the
    same block size to stay bit-matched with serving. Masked ticks
    contribute exactly-zero terms, so frozen lanes stay value-frozen.

    Every reduction is the same broadcast-multiply + trailing-axis sum as
    `rls_update` (batch-width bit-stability), and XLA fuses the multiplies
    into the reduces, so no (E, S, S, K) temporary is materialized.
    """
    k_ticks = xb.shape[0]
    # learn math never runs reduced: see the module precision note
    xb = xb.astype(p.dtype)
    y = y.astype(p.dtype)
    dt_one = p.dtype.type(1.0)
    # B[e, i, t] = sum_j P[e, i, j] x_t[e, j] — one pass over P, as a
    # batched GEMM. Unlike a batched mat-VEC (whose reduction order shifts
    # with the batch width — the reason rls_update is mul+sum), a batched
    # matmul runs one fixed-shape (S, S) x (S, K) GEMM per lane, so lane
    # results are bit-identical at any E (pinned by
    # tests/test_rls_learning.py); the mul+sum spelling of this op
    # materializes a (E, S, K, S) temp on CPU and measured ~27x slower.
    # K == 1 is the degenerate case where the GEMM IS a mat-vec — there the
    # mul+sum spelling is both batch-stable and cheap, so use it.
    if k_ticks == 1:
        b = jnp.sum(p * xb[0][:, None, :], axis=-1)[:, :, None]  # (E, S, 1)
    else:
        xk = jnp.transpose(xb, (1, 0, 2))  # (E, K, S)
        b = jnp.einsum("eij,etj->eit", p, xk, precision=EXACT_MATMUL)  # (E, S, K)

    # gst / pxst grow one (E, 1, S) row per tick — batching each tick's
    # corrections against ALL prior pairs keeps the unrolled op count O(K)
    # instead of O(K^2) (the small-N regime is op-count-bound, not
    # bandwidth-bound)
    gst = pxst = None  # (E, t, S) stacks of gains / px vectors
    preds = []
    if lam != 1.0:
        inv_lam = p.dtype.type(1.0 / lam)
        cum = jnp.ones(p.shape[0], p.dtype)  # (E,) prod of per-tick 1/lam_e
        coefs = None  # (E, t): current coefficient of each stored pair
    w_t = w
    for t in range(k_ticks):
        x_t = xb[t]  # (E, S)
        px_t = b[:, :, t] if lam == 1.0 else cum[:, None] * b[:, :, t]
        if t:
            c = jnp.sum(pxst * x_t[:, None, :], axis=-1)  # (E, t) px_j . x_t
            if lam != 1.0:
                c = coefs * c
            px_t = px_t - jnp.sum(c[:, :, None] * gst, axis=1)
        denom = lam + jnp.sum(x_t * px_t, axis=-1)  # (E,)
        k_t = jnp.where(mask[t][:, None], px_t / denom[:, None], 0.0)
        pred_t = jnp.sum(w_t * x_t[:, :, None], axis=1)  # (E, n_out)
        w_t = w_t + k_t[:, :, None] * (y[t] - pred_t)[:, None, :]
        preds.append(pred_t)
        if gst is None:
            gst, pxst = k_t[:, None, :], px_t[:, None, :]
        else:
            gst = jnp.concatenate([gst, k_t[:, None, :]], axis=1)
            pxst = jnp.concatenate([pxst, px_t[:, None, :]], axis=1)
        if lam != 1.0:
            u_t = jnp.where(mask[t], inv_lam, dt_one)  # (E,)
            coefs = (
                u_t[:, None]
                if coefs is None
                else jnp.concatenate([coefs * u_t[:, None], u_t[:, None]], axis=1)
            )
            cum = cum * u_t
    # P' = cum P - sum_t coef_t k_t px_t^T: one read + write of P, again as
    # a batched fixed-shape GEMM (lane-stable; the mul+sum spelling fuses
    # catastrophically with the stacked loop outputs — ~8x slower measured)
    if lam != 1.0:
        gst = coefs[:, :, None] * gst
    p_scaled = p if lam == 1.0 else cum[:, None, None] * p
    p_new = p_scaled - jnp.einsum(
        "eti,etj->eij", gst, pxst, precision=EXACT_MATMUL
    )
    return p_new, w_t, jnp.stack(preds)  # (E,S,S), (E,S,O), (K,E,O)


# ---------------------------------------------------------------------------
# LMS (normalized least mean squares) — the O(S) learner behind
# ExecPlan.learn="lms"
# ---------------------------------------------------------------------------
#
# RLS pays O(S^2) state (the (E, S, S) inverse-Gram P) and O(S^2) work per
# tick for exact recursive ridge. LMS is the classic cheap alternative: a
# stochastic-gradient step on the instantaneous squared error,
#
#     pred = W^T x
#     e    = y - pred
#     W'   = W + mu * x e^T / (eps + ||x||^2)        (NLMS normalization)
#
# O(S) state per output column and O(S) work per tick — the fitness signal
# the tune/ subsystem wants at large S, where allocating E (N+1)^2 P blocks
# per candidate would dominate the search itself. The ||x||^2 normalization
# (NLMS) makes the stable step-size range input-scale-free: 0 < mu < 2
# regardless of the state magnitudes, the standard result for normalized
# LMS. eps = 1e-8 guards all-zero feature rows (washout-padded ticks).
#
# Like rls_update, every reduction is broadcast-multiply + trailing-axis
# sum, so lane results are bit-identical at any batch width E — that is
# what lets a served lane bit-match the E=1 offline oracle
# (core.reservoir.fit_lms). Masked ticks fold into the gain (step = 0), so
# frozen lanes stay value-frozen, and because the update is per-tick local
# (no cross-tick P recursion), chunked application is the SAME op sequence
# at any chunk size — fit_lms needs no `block` parameter.

_LMS_EPS = 1e-8


def lms_init(e: int, n_state: int, n_out: int, dtype) -> jnp.ndarray:
    """Fresh per-lane LMS weights: W = 0, shape (E, S, n_out)."""
    return jnp.zeros((e, n_state, n_out), dtype)


def lms_update(
    w: jnp.ndarray,  # (E, S, n_out) readout weights per lane
    x: jnp.ndarray,  # (E, S) this tick's feature vector per lane
    y: jnp.ndarray,  # (E, n_out) this tick's target per lane
    mask: jnp.ndarray,  # (E,) bool; False lanes return w value-frozen
    mu: float,  # STATIC step size in (0, 2) (a Python float)
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One masked batched NLMS step -> (W', a-priori predictions (E, n_out)).

    Same contract as `rls_update`: predictions use the INCOMING weights;
    masked-off lanes keep W value-frozen but still predict.
    """
    # learn math never runs reduced: see the module precision note
    x = x.astype(w.dtype)
    y = y.astype(w.dtype)
    pred = jnp.sum(w * x[:, :, None], axis=1)  # (E, n_out)
    err = y - pred
    norm = jnp.sum(x * x, axis=-1) + w.dtype.type(_LMS_EPS)  # (E,)
    g = jnp.where(mask, mu / norm, 0.0)  # (E,) masked gain
    w_new = w + (g[:, None] * x)[:, :, None] * err[:, None, :]
    return w_new, pred


def lms_chunk(
    w: jnp.ndarray,  # (E, S, n_out) readout weights per lane
    xb: jnp.ndarray,  # (K, E, S) feature vectors, one row per tick
    y: jnp.ndarray,  # (K, E, n_out) targets per tick
    mask: jnp.ndarray,  # (K, E) bool; False ticks leave w value-frozen
    mu: float,  # STATIC step size in (0, 2)
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """K sequential NLMS steps -> (W', a-priori preds (K, E, n_out)).

    A lax.scan of `lms_update` over the chunk's ticks: unlike RLS there is
    no O(S^2) P block to amortize, so the per-tick recursion IS the cheap
    spelling — O(K * S) work, O(S) state. The per-tick op sequence is
    exactly `lms_update`'s, so chunked serving at any chunk_ticks is
    bit-identical to per-tick application (and to the offline
    `core.reservoir.fit_lms` oracle at E = 1).
    """
    xb = xb.astype(w.dtype)
    y = y.astype(w.dtype)

    def tick(w_c, rows):
        x_t, y_t, m_t = rows
        w_n, pred = lms_update(w_c, x_t, y_t, m_t, mu)
        return w_n, pred

    w_fin, preds = jax.lax.scan(tick, w, (xb, y, mask))
    return w_fin, preds  # (E, S, n_out), (K, E, n_out)
