"""Physical constants and the paper's parameter set (Table 1).

Units follow the paper (Gaussian/CGS for magnetics, SI for charge/current):
  gamma   [rad / (Oe s)]      gyromagnetic ratio
  Ms      [emu / cm^3]        saturation magnetization
  fields  [Oe]
  volume  [cm^3]
  current [A]

The spin-transfer field H_s = hbar * eta * I / (2 e (1 + lam m.p) Ms V) mixes
SI (hbar, e, I) and CGS (Ms, V): hbar*I/(2e) is in Joule; Ms*V is in emu =
erg/Oe; 1 J = 1e7 erg, hence the explicit ERG_PER_JOULE factor. With the
paper's values H_s(m.p=0) ~ 135 Oe, comparable to H_appl = 200 Oe.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

# Precision of every f32 matmul on the reservoir's path. On a TPU, XLA's
# default for an f32 dot is a single bf16 pass; HIGHEST keeps f32 operands
# f32 there, and changes nothing on the CPU. Reduced-precision policies
# (ExecPlan.precision) cast their operands to bf16 first, so they stay one
# bf16 pass.
EXACT_MATMUL = jax.lax.Precision.HIGHEST

# Fundamental constants (paper Table 1).
HBAR = 1.05457266e-34  # J s
E_CHARGE = 1.60217733e-19  # C
ERG_PER_JOULE = 1.0e7

# Paper Table 1 values.
GAMMA = 1.764e7  # rad / (Oe s)
ALPHA = 0.005
MS = 1448.3  # emu / cm^3
HK = 18.616e3  # Oe (interfacial anisotropy field)
HAPPL = 200.0  # Oe (applied field)
ETA = 0.537  # spin polarization
LAMBDA = 0.288  # spin-transfer torque asymmetry
CURRENT = 2.5e-3  # A
VOLUME = math.pi * 60.0**2 * 2.0 * 1e-21  # cm^3  (pi * 60^2 * 2 nm^3)
P_PINNED = (1.0, 0.0, 6.123234e-17)  # pinned-layer direction (~e_x)
A_CP = 1.0  # Oe, coupling amplitude
A_IN = 1.0  # Oe, input amplitude

# Benchmark protocol (paper §3.2).
DT = 1.0e-11  # s
N_STEPS_PAPER = 500_000
PHI0 = 2.0 * math.pi / 360.0  # initial-condition angle


class STOParams(NamedTuple):
    """LLG/STO parameters as a pytree of scalars (vmap-able for ensembles).

    All leaves are jnp scalars (or broadcastable arrays with a leading
    ensemble axis) so `jax.vmap`/`shard_map` can sweep any subset of them.
    """

    gamma: jnp.ndarray
    alpha: jnp.ndarray
    ms: jnp.ndarray
    hk: jnp.ndarray
    happl: jnp.ndarray
    eta: jnp.ndarray
    lam: jnp.ndarray
    current: jnp.ndarray
    volume: jnp.ndarray
    a_cp: jnp.ndarray
    a_in: jnp.ndarray
    px: jnp.ndarray
    py: jnp.ndarray
    pz: jnp.ndarray

    @property
    def llg_prefactor(self):
        """gamma / (1 + alpha^2)."""
        return self.gamma / (1.0 + self.alpha**2)

    @property
    def hs_coef(self):
        """H_s numerator in Oe: 1e7 * hbar * eta * I / (2 e Ms V).

        H_s(m) = hs_coef / (1 + lam * m.p).
        """
        return (
            ERG_PER_JOULE
            * HBAR
            * self.eta
            * self.current
            / (2.0 * E_CHARGE * self.ms * self.volume)
        )

    @property
    def demag_field(self):
        """Effective perpendicular anisotropy: Hk - 4 pi Ms  [Oe]."""
        return self.hk - 4.0 * math.pi * self.ms


def default_params(dtype=jnp.float32) -> STOParams:
    """The paper's Table 1 parameter set."""
    as_ = lambda v: jnp.asarray(v, dtype=dtype)
    return STOParams(
        gamma=as_(GAMMA),
        alpha=as_(ALPHA),
        ms=as_(MS),
        hk=as_(HK),
        happl=as_(HAPPL),
        eta=as_(ETA),
        lam=as_(LAMBDA),
        current=as_(CURRENT),
        volume=as_(VOLUME),
        a_cp=as_(A_CP),
        a_in=as_(A_IN),
        px=as_(P_PINNED[0]),
        py=as_(P_PINNED[1]),
        pz=as_(P_PINNED[2]),
    )


def initial_magnetization(n: int, dtype=jnp.float32, phi0: float = PHI0) -> jnp.ndarray:
    """Paper Eq. (4): identical unit-norm initial state for every oscillator.

    Returns m0 with shape (n, 3); |m0_k| = 1 exactly (up to dtype rounding).
    """
    m0 = jnp.array(
        [
            math.sin(phi0) * math.cos(phi0),
            math.sin(phi0) * math.sin(phi0),
            math.cos(phi0),
        ],
        dtype=dtype,
    )
    return jnp.broadcast_to(m0, (n, 3))
