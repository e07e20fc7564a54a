"""Reservoir-computing API on top of the coupled-STO integrator.

Pipeline (the paper's application context, [AKT+22]):
  input series u(t)  --drive-->  node states x_t = m^x(t_k)  --fit-->  readout

Only the linear readout is trained, which is what makes reservoir
computing cheap; the expensive part — and the paper's subject — is the
simulation of the reservoir itself (`drive()`, now a shim over
repro.api.compile_plan). Two trainers are provided: `fit_ridge` (batch
ridge regression) and `fit_rls` (recursive least squares — the offline
oracle for the serving engine's streaming online learning,
`ExecPlan.learn="rls"`).
"""

from __future__ import annotations

import warnings
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import constants, coupling
from repro.core.constants import STOParams


class Reservoir(NamedTuple):
    params: STOParams
    w_cp: jnp.ndarray  # (N, N)
    w_in: jnp.ndarray  # (N, N_in)
    m0: jnp.ndarray  # (N, 3)
    dt: float
    hold_steps: int  # integration steps per input sample


def make_reservoir(
    n: int,
    n_in: int = 1,
    seed: int = 0,
    dt: float = constants.DT,
    hold_steps: int = 100,
    dtype=jnp.float32,
    params: Optional[STOParams] = None,
) -> Reservoir:
    if params is None:
        params = constants.default_params(dtype)
    w_cp = jnp.asarray(coupling.make_coupling_matrix(n, seed=seed), dtype=dtype)
    w_in = jnp.asarray(coupling.make_input_matrix(n, n_in, seed=seed + 1), dtype=dtype)
    m0 = constants.initial_magnetization(n, dtype=dtype)
    return Reservoir(params, w_cp, w_in, m0, dt, hold_steps)


def coerce_input_series(u_seq: jnp.ndarray, n_in: int, dtype, xp=jnp) -> jnp.ndarray:
    """Validate an input series against the explicit (T, N_in) contract.

    Accepts (T, N_in), or 1-D (T,) when n_in == 1. Anything else — including
    the previously silently-transposed (1, T) — raises with the expected
    shape spelled out. Shared by `drive` and the serving engine so both
    enforce the same contract. xp=numpy keeps the series host-side (the
    serving engine assembles u blocks on host; a device round-trip per
    submit is pure overhead).
    """
    u_seq = xp.asarray(u_seq, dtype=dtype)
    if u_seq.ndim == 1:
        if n_in != 1:
            raise ValueError(
                f"1-D input series is only valid for n_in == 1; this "
                f"reservoir has n_in == {n_in}. Pass shape (T, {n_in})."
            )
        return u_seq[:, None]
    if u_seq.ndim != 2 or u_seq.shape[1] != n_in:
        raise ValueError(
            f"input series must have shape (T, {n_in}) — one row per sample, "
            f"one column per input channel — or (T,) when n_in == 1; got "
            f"{u_seq.shape}. A (1, T) series must be passed as (T, 1)."
        )
    return u_seq


def drive(
    res: Reservoir,
    u_seq: jnp.ndarray,
    m0: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Run the reservoir over an input series. Returns (final m, states (T,N)).

    .. deprecated:: thin shim over the unified execution API. New code:

        sim = repro.api.compile_plan(repro.api.SimSpec.from_reservoir(res),
                                     impl="scan")
        mT, states = sim.drive(u_seq, m0=m0)

    The shim compiles an impl="scan" plan, which runs the exact op sequence
    this function always ran — results are bit-identical. u_seq follows the
    explicit (T, N_in) contract ((T,) allowed for n_in == 1); m0 optionally
    resumes integration from an arbitrary (N, 3) magnetization state, and
    driving in chunks with the carried-over final state is exactly
    equivalent to one long drive.
    """
    warnings.warn(
        "repro.core.reservoir.drive is deprecated; use "
        "repro.api.compile_plan(SimSpec.from_reservoir(res), impl='scan').drive(...)",
        DeprecationWarning,
        stacklevel=2,
    )
    from repro import api

    sim = api.compile_plan(api.SimSpec.from_reservoir(res), impl="scan")
    return sim.drive(u_seq, m0=m0)


class Readout(NamedTuple):
    w_out: jnp.ndarray  # (N + 1, n_out) — last row is the bias
    washout: int


def fit_ridge(
    states: jnp.ndarray,  # (T, N)
    targets: jnp.ndarray,  # (T, n_out) or (T,)
    washout: int = 0,
    reg: float = 1e-6,
) -> Readout:
    """Ridge regression readout: solve (X^T X + reg I) W = X^T Y.

    targets follows an explicit shape contract mirroring
    `coerce_input_series`: (T, n_out) — one row per sample, aligned with
    states (T, N) — or 1-D (T,) for a single output. A (1, T) row vector is
    rejected rather than silently transposed (the old auto-transpose also
    mangled legitimate single-sample (1, n_out) targets).

    The Gram matrix is accumulated in f32/f64 regardless of state dtype; the
    solve is tiny ((N+1)^2) next to the simulation cost.
    """
    states = jnp.asarray(states)
    targets = jnp.asarray(targets)
    t = states.shape[0]
    if targets.ndim == 1:
        targets = targets[:, None]
    if targets.ndim != 2 or targets.shape[0] != t:
        raise ValueError(
            f"targets must have shape ({t}, n_out) — one row per state "
            f"sample — or ({t},) for a single output; got "
            f"{tuple(targets.shape)} against states {tuple(states.shape)}. "
            f"A (1, T) row vector must be passed as (T,) or (T, 1)."
        )
    x = states[washout:]
    y = targets[washout:].astype(jnp.float64 if x.dtype == jnp.float64 else jnp.float32)
    x = x.astype(y.dtype)
    ones = jnp.ones((x.shape[0], 1), dtype=x.dtype)
    xb = jnp.concatenate([x, ones], axis=1)  # (T', N+1)
    gram = jnp.matmul(xb.T, xb, precision=constants.EXACT_MATMUL)
    rhs = jnp.matmul(xb.T, y, precision=constants.EXACT_MATMUL)
    w = jnp.linalg.solve(gram + reg * jnp.eye(gram.shape[0], dtype=gram.dtype), rhs)
    return Readout(w_out=w, washout=washout)


def fit_rls(
    states: jnp.ndarray,  # (T, N)
    targets: jnp.ndarray,  # (T, n_out) or (T,)
    washout: int = 0,
    reg: float = 1e-6,
    lam: float = 1.0,
    w0: Optional[jnp.ndarray] = None,  # (N + 1, n_out) warm start
    block: int = 1,
) -> Readout:
    """Recursive-least-squares readout — the offline oracle for streaming
    online learning (`ExecPlan.learn="rls"`).

    Processes the state rows sequentially with the same update kernels the
    serving engine fuses into `CompiledSim.tick_chunk` (kernels/rls.py), at
    batch width 1: P starts at I / reg, weights at w0 (zeros by default),
    and the first `washout` rows are masked — the update is skipped with
    exactly-zero contributions, mirroring a streaming session's
    `learn_washout` ticks.

    block matches the serving engine's chunk size: `block=K` applies
    `kernels.rls.rls_chunk` to K-row blocks [0, K), [K, 2K), ... — exactly
    how a served session's ticks are blocked (sessions admit at chunk
    boundaries, so their local blocking is origin-aligned regardless of
    global chunk phase). Fed a session's HARVESTED states
    (`SessionResult.states`) with block == the engine's chunk_ticks, this
    reproduces the session's learned readout bit-for-bit on the scan
    backend — the update kernels are reduction-order stable across batch
    widths (see kernels/rls.py) — pinned by tests/test_rls_learning.py.
    (The harvested states, not a solo re-drive: batched integration agrees
    with solo runs only to float tolerance. And the same block size: the
    chunked recursion is mathematically identical to block=1 but orders
    float ops differently.)

    With lam == 1.0 the recursion solves the same regularized normal
    equations as `fit_ridge(states, targets, washout, reg)` — identical up
    to float roundoff (RLS runs in the state dtype; fit_ridge accumulates
    its Gram matrix separately). lam < 1 exponentially forgets old samples
    (non-stationary targets), which batch ridge cannot express.

    targets follows `fit_ridge`'s explicit shape contract: (T, n_out)
    aligned with states, or (T,) for a single output.
    """
    from repro.kernels import rls as krls

    states = jnp.asarray(states)
    targets = jnp.asarray(targets)
    t = states.shape[0]
    if targets.ndim == 1:
        targets = targets[:, None]
    if targets.ndim != 2 or targets.shape[0] != t:
        raise ValueError(
            f"targets must have shape ({t}, n_out) — one row per state "
            f"sample — or ({t},) for a single output; got "
            f"{tuple(targets.shape)} against states {tuple(states.shape)}."
        )
    if not 0.0 < float(lam) <= 1.0:
        raise ValueError(f"lam (forgetting factor) must be in (0, 1]; got {lam}")
    if block < 1:
        raise ValueError(f"block must be an int >= 1; got {block}")
    dtype = states.dtype
    n_state = states.shape[1] + 1
    n_out = targets.shape[1]
    xb = jnp.concatenate([states, jnp.ones((t, 1), dtype)], axis=1)  # (T, S)
    y = targets.astype(dtype)
    mask = jnp.arange(t) >= washout
    p0, w_init = krls.rls_init(1, n_state, n_out, reg, dtype)
    if w0 is not None:
        w_init = jnp.asarray(w0, dtype).reshape(1, n_state, n_out)
    lam_c = float(lam)  # static, like the streaming workers (kernels/rls.py)

    # every block size — including 1 — goes through rls_chunk, because the
    # serving engine does too (tick_chunk's learn tail is rls_chunk at any
    # chunk_ticks): the oracle must run the IDENTICAL op sequence or the
    # bit-match contract would silently fail at chunk_ticks == 1.
    # Pad the tail to a whole block with masked rows (exactly-zero
    # contributions, like a served session's trailing masked chunk rows).
    pad = (-t) % block
    if pad:
        xb = jnp.concatenate([xb, jnp.zeros((pad, n_state), dtype)])
        y = jnp.concatenate([y, jnp.zeros((pad, n_out), dtype)])
        mask = jnp.concatenate([mask, jnp.zeros(pad, bool)])
    nb = xb.shape[0] // block

    def blk(carry, rows):
        p, w = carry
        x_r, y_r, m_r = rows  # (block, S), (block, n_out), (block,)
        p, w, preds = krls.rls_chunk(
            p, w, x_r[:, None, :], y_r[:, None, :], m_r[:, None], lam_c
        )
        return (p, w), preds[:, 0]

    (_, w_fin), _ = jax.lax.scan(
        blk,
        (p0, w_init),
        (
            xb.reshape(nb, block, n_state),
            y.reshape(nb, block, n_out),
            mask.reshape(nb, block),
        ),
    )
    return Readout(w_out=w_fin[0], washout=washout)


def fit_lms(
    states: jnp.ndarray,  # (T, N)
    targets: jnp.ndarray,  # (T, n_out) or (T,)
    washout: int = 0,
    mu: float = 0.5,
    w0: Optional[jnp.ndarray] = None,  # (N + 1, n_out) warm start
) -> Readout:
    """Normalized-LMS readout — the offline oracle for streaming online
    learning with `ExecPlan.learn="lms"`.

    Processes the state rows sequentially with the same update kernel the
    serving engine fuses into `CompiledSim.tick_chunk`
    (kernels/rls.py::lms_chunk) at batch width 1: weights start at w0
    (zeros by default) and the first `washout` rows are masked (exactly-
    zero steps), mirroring a streaming session's `learn_washout` ticks.

    Unlike `fit_rls` there is no `block` parameter: the LMS recursion has
    no cross-tick P block, so chunked application at ANY chunk_ticks runs
    the identical per-tick op sequence — fed a session's HARVESTED states
    (`SessionResult.states`), this reproduces the session's learned
    readout bit-for-bit on the scan backend regardless of the engine's
    chunk size (the update kernel is reduction-order stable across batch
    widths; see kernels/rls.py).

    LMS is a stochastic-gradient approximation: it converges toward the
    ridge solution but does not equal it in finite samples — use it where
    the O(S) per-tick cost matters (large S, or many `repro.tune`
    candidates), and RLS/ridge where exactness does.
    """
    from repro.kernels import rls as krls

    states = jnp.asarray(states)
    targets = jnp.asarray(targets)
    t = states.shape[0]
    if targets.ndim == 1:
        targets = targets[:, None]
    if targets.ndim != 2 or targets.shape[0] != t:
        raise ValueError(
            f"targets must have shape ({t}, n_out) — one row per state "
            f"sample — or ({t},) for a single output; got "
            f"{tuple(targets.shape)} against states {tuple(states.shape)}."
        )
    if not 0.0 < float(mu) < 2.0:
        raise ValueError(f"mu (NLMS step size) must be in (0, 2); got {mu}")
    dtype = states.dtype
    n_state = states.shape[1] + 1
    n_out = targets.shape[1]
    xb = jnp.concatenate([states, jnp.ones((t, 1), dtype)], axis=1)  # (T, S)
    y = targets.astype(dtype)
    mask = jnp.arange(t) >= washout
    w_init = krls.lms_init(1, n_state, n_out, dtype)
    if w0 is not None:
        w_init = jnp.asarray(w0, dtype).reshape(1, n_state, n_out)
    w_fin, _ = krls.lms_chunk(
        w_init, xb[:, None, :], y[:, None, :], mask[:, None], float(mu)
    )
    return Readout(w_out=w_fin[0], washout=washout)


def predict(readout: Readout, states: jnp.ndarray) -> jnp.ndarray:
    x = states[readout.washout :]
    ones = jnp.ones((x.shape[0], 1), dtype=x.dtype)
    xb = jnp.concatenate([x, ones], axis=1).astype(readout.w_out.dtype)
    return jnp.matmul(xb, readout.w_out, precision=constants.EXACT_MATMUL)


def nmse(pred: jnp.ndarray, target: jnp.ndarray) -> float:
    target = jnp.reshape(target, pred.shape).astype(pred.dtype)
    num = jnp.mean((pred - target) ** 2)
    den = jnp.var(target) + 1e-30
    return float(num / den)
