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
"""

from __future__ import annotations

import functools
import math

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


@functools.partial(jax.jit, static_argnames=("hold_steps",))
def _drive(c, w, w_in, m0, u, lengths, dt, hold_steps):
    """m0 (3, N, S), u (T, S, n_in), lengths (S,) -> (m_T (3, N, S), x (T, N, S))."""
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

    def tick(m, xs):
        t, u_t = xs
        h_in = c["a_in"] * _matmul(w_in, u_t.T)  # (N, S)
        m_new = jax.lax.fori_loop(
            0, hold_steps, functools.partial(rk4, h_in=h_in), m
        )
        m_new = jnp.where((t < lengths)[None, None, :], m_new, m)
        return m_new, m_new[0]

    t = jnp.arange(u.shape[0])
    return jax.lax.scan(tick, m0, (t, u))


def drive(data: dict, inputs, lengths, readouts, starts=None, block: int = 16):
    """Run the reference over sessions, `block` sessions at a time.

    inputs: list of (T_s, n_in) float32 series; lengths: their T_s;
    readouts: a list of (N+1, n_out) weights; starts: None, or per session
    None or an (N, 3) state to start from in place of the deployment's m0.
    Returns per session a dict with `states` (T_s, N), `final_m` (N, 3) and
    `outputs` (T_s, n_out), all numpy.
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
        m_t, x = _drive(c, w, w_in, jnp.asarray(m0_b), jnp.asarray(u),
                        jnp.asarray(lens), data["dt"], data["hold_steps"])
        m_t = np.asarray(m_t)
        x = np.asarray(x)
        for j, i in enumerate(idx):
            states = x[: lengths[i], :, j]
            wo = np.asarray(readouts[i], np.float64)
            outputs = states.astype(np.float64) @ wo[:-1] + wo[-1]
            out.append({
                "states": states,
                "final_m": m_t[:, :, j].T.copy(),
                "outputs": outputs,
            })
    return out
