"""Plain reference for the coupled spin-torque-oscillator reservoir.

The paper's equations (arXiv:2312.01121, Eq. 1-4) written out once in
straightforward `jax.numpy` float32, with no kernels, slots or chunks. It
imports nothing of the program under test and takes nothing it made: the
coupling and input matrices, the initial state, the parameters and the
readouts all come from the benchmark's own data (`benchlib.data`).

    dm/dt = -pref m x b - alpha pref m x (m x b),   pref = gamma / (1 + alpha^2)
    b     = (h_x, 0, Happl + (Hk - 4 pi Ms) m_z) + H_s p x m
    h_x   = a_cp (W m_x) + a_in (W_in u)             H_s = hs / (1 + lam m.p)

Each input sample is held for `hold_steps` classical RK4 steps of `dt`; a
session's state after tick t is m_x of every oscillator. A session that has
run out of input keeps its state, as a retired lane does. The matrix
products keep float32 operands at Precision.HIGHEST.

A session may also learn its readout online, by plain recursive least
squares, one update per tick after the state (float32, HIGHEST):

    x = [m_x ; 1]                     features, S = N + 1
    y_hat = W^T x                     prediction, with the incoming W
    for t >= washout:
      k = P x / (lam + x^T P x)
      W <- W + k (y - y_hat)^T
      P <- (P - k (x^T P)) / lam

from P = I / reg and W = 0, or from a given (P, W). Written from these
equations, not from the program's blocked form; where the program's
semantics could differ it is matched: the prediction of every tick is
returned, washout ticks included (they use the starting W), and a session
that has run out of input neither predicts nor updates.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

HBAR = 1.05457266e-34  # J s
E_CHARGE = 1.60217733e-19  # C
ERG_PER_JOULE = 1.0e7


def constants(params: dict) -> dict:
    """The field's scalar coefficients from the Table-1 parameters, in float64."""
    p = {k: float(v) for k, v in params.items()}
    return {
        "pref": p["gamma"] / (1.0 + p["alpha"] ** 2),
        "alpha": p["alpha"],
        "hs": ERG_PER_JOULE * HBAR * p["eta"] * p["current"]
        / (2.0 * E_CHARGE * p["ms"] * p["volume"]),
        "lam": p["lam"],
        "happl": p["happl"],
        "demag": p["hk"] - 4.0 * math.pi * p["ms"],
        "a_cp": p["a_cp"],
        "a_in": p["a_in"],
        "px": p["px"],
        "py": p["py"],
        "pz": p["pz"],
    }


def _matmul(a, b):
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def _rls_tick(p, wl, x, y, live, lam):
    """One RLS update of every lane: p (B, S, S), wl (B, S, n_out), x (B, S),
    y (B, n_out), live (B,) -> (p, wl, a-priori prediction (B, n_out))."""
    hi = jax.lax.Precision.HIGHEST
    pred = jnp.einsum("bso,bs->bo", wl, x, precision=hi)
    px = jnp.einsum("bij,bj->bi", p, x, precision=hi)
    xp = jnp.einsum("bj,bji->bi", x, p, precision=hi)
    k = px / (lam + jnp.sum(x * px, axis=-1))[:, None]
    k = jnp.where(live[:, None], k, 0.0)
    wl = wl + k[:, :, None] * (y - pred)[:, None, :]
    lam_b = jnp.where(live, lam, jnp.float32(1.0))  # a lane not learning keeps P
    p = (p - k[:, :, None] * xp[:, None, :]) / lam_b[:, None, None]
    return p, wl, pred


@functools.partial(jax.jit, static_argnames=("hold_steps",))
def _drive(c, w, w_in, m0, u, lengths, dt, hold_steps, learn=None):
    """m0 (3, N, S), u (T, S, n_in), lengths (S,) -> (m_T (3, N, S), x (T, N, S)),
    and with `learn` = (p0 (S, N+1, N+1), w0 (S, N+1, n_out), y (T, S, n_out),
    skip (S,), lam) also W_T and the predictions (T, S, n_out)."""
    f32 = jnp.float32
    c = {k: jnp.asarray(v, f32) for k, v in c.items()}
    dt = jnp.asarray(dt, f32)

    def field(m, h_in):
        mx, my, mz = m[0], m[1], m[2]
        hx = c["a_cp"] * _matmul(w, mx) + h_in
        hz = c["happl"] + c["demag"] * mz
        hs = c["hs"] / (1.0 + c["lam"] * (mx * c["px"] + my * c["py"] + mz * c["pz"]))
        # b = (hx, 0, hz) + hs * (p x m)
        bx = hx + hs * (c["py"] * mz - c["pz"] * my)
        by = hs * (c["pz"] * mx - c["px"] * mz)
        bz = hz + hs * (c["px"] * my - c["py"] * mx)
        # m x b, then m x (m x b)
        ax = my * bz - mz * by
        ay = mz * bx - mx * bz
        az = mx * by - my * bx
        cx = my * az - mz * ay
        cy = mz * ax - mx * az
        cz = mx * ay - my * ax
        k = c["pref"]
        ka = c["alpha"] * c["pref"]
        return jnp.stack([-k * ax - ka * cx, -k * ay - ka * cy, -k * az - ka * cz])

    def rk4(_, y, h_in):
        k1 = field(y, h_in)
        k2 = field(y + (dt * 0.5) * k1, h_in)
        k3 = field(y + (dt * 0.5) * k2, h_in)
        k4 = field(y + dt * k3, h_in)
        return y + (dt / 6.0) * (k1 + 2.0 * k2 + 2.0 * k3 + k4)

    def integrate(m, t, u_t):
        h_in = c["a_in"] * _matmul(w_in, u_t.T)  # (N, S)
        m_new = jax.lax.fori_loop(
            0, hold_steps, functools.partial(rk4, h_in=h_in), m
        )
        return jnp.where((t < lengths)[None, None, :], m_new, m)

    t = jnp.arange(u.shape[0])
    if learn is None:
        def tick(m, xs):
            m_new = integrate(m, *xs)
            return m_new, m_new[0]

        return jax.lax.scan(tick, m0, (t, u))

    p0, w0, y, skip, lam = learn
    lam = jnp.asarray(lam, f32)

    def learn_tick(carry, xs):
        m, p, wl = carry
        t_, u_t, y_t = xs
        m_new = integrate(m, t_, u_t)
        x = jnp.concatenate([m_new[0].T, jnp.ones((m.shape[2], 1), f32)], axis=1)
        live = (t_ >= skip) & (t_ < lengths)
        p, wl, pred = _rls_tick(p, wl, x, y_t, live, lam)
        return (m_new, p, wl), (m_new[0], pred)

    (m_t, _, w_t), (x, preds) = jax.lax.scan(learn_tick, (m0, p0, w0), (t, u, y))
    return m_t, x, w_t, preds


def _learn_block(learner, idx, n, block, t_max):
    """The learner's arrays for the sessions `idx`, padded to `block` lanes:
    (p0, w0, targets (T, block, n_out), skip, lam)."""
    s = n + 1
    n_out = np.shape(learner["targets"][idx[0]])[1]
    p0 = np.broadcast_to(np.eye(s, dtype=np.float32) / np.float32(learner["reg"]),
                         (block, s, s)).copy()
    w0 = np.zeros((block, s, n_out), np.float32)
    y = np.zeros((t_max, block, n_out), np.float32)
    skip = np.zeros((block,), np.int32)
    for j, i in enumerate(idx):
        if learner.get("p0") is not None and learner["p0"][i] is not None:
            p0[j] = learner["p0"][i]
        if learner.get("w0") is not None and learner["w0"][i] is not None:
            w0[j] = learner["w0"][i]
        tgt = np.asarray(learner["targets"][i], np.float32)
        y[: len(tgt), j] = tgt
        skip[j] = learner["skip"][i]
    return (jnp.asarray(p0), jnp.asarray(w0), jnp.asarray(y), jnp.asarray(skip),
            float(learner["lam"]))


def drive(data: dict, inputs, lengths, readouts=None, starts=None, block: int = 16,
          learner: Optional[dict] = None):
    """Run the reference over sessions, `block` sessions at a time.

    inputs: list of (T_s, n_in) float32 series; lengths: their T_s;
    readouts: None, or a list of (N+1, n_out) weights; starts: None, or per
    session None or an (N, 3) state to start from in place of the
    deployment's m0. learner: None, or a dict with `lam`, `reg`, per session
    `targets` (T_s, n_out) and `skip` (the ticks of this run before the
    first update), and optionally per session `p0` (N+1, N+1) and `w0`
    (N+1, n_out) to start from (None: I / reg and 0).
    Returns per session a dict with `states` (T_s, N), `final_m` (N, 3),
    with readouts `outputs` (T_s, n_out), and with a learner `predictions`
    (T_s, n_out) and `final_w` (N+1, n_out), all numpy.
    """
    n = data["w"].shape[0]
    n_in = data["w_in"].shape[1]
    c = constants(data["params"])
    w = jnp.asarray(data["w"], jnp.float32)
    w_in = jnp.asarray(data["w_in"], jnp.float32)
    m0 = np.asarray(data["m0"], np.float32).T  # (3, N)
    t_max = int(max(lengths))
    out = []
    for lo in range(0, len(inputs), block):
        idx = list(range(lo, min(lo + block, len(inputs))))
        u = np.zeros((t_max, block, n_in), np.float32)
        lens = np.zeros((block,), np.int32)
        for j, i in enumerate(idx):
            u[: lengths[i], j] = inputs[i]
            lens[j] = lengths[i]
        m0_b = np.repeat(m0[:, :, None], block, axis=2)
        for j, i in enumerate(idx):
            if starts is not None and starts[i] is not None:
                m0_b[:, :, j] = np.asarray(starts[i], np.float32).T
        learn = None if learner is None else _learn_block(learner, idx, n, block, t_max)
        res = _drive(c, w, w_in, jnp.asarray(m0_b), jnp.asarray(u),
                     jnp.asarray(lens), data["dt"], data["hold_steps"], learn)
        m_t, x = np.asarray(res[0]), np.asarray(res[1])
        if learner is not None:
            w_t, preds = np.asarray(res[2]), np.asarray(res[3])
        del res, learn
        for j, i in enumerate(idx):
            states = x[: lengths[i], :, j]
            got = {"states": states, "final_m": m_t[:, :, j].T.copy()}
            if readouts is not None:
                wo = np.asarray(readouts[i], np.float64)
                got["outputs"] = states.astype(np.float64) @ wo[:-1] + wo[-1]
            if learner is not None:
                got["predictions"] = preds[: lengths[i], j]
                got["final_w"] = w_t[j]
            out.append(got)
    return out
