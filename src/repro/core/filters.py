"""Passive input filter (paper §5.1) as an exact discrete state-space system.

The circuit (paper Fig. 5) is a second-order LC low-pass between the DC
busbar and the rack node, with an R-L damping leg in *parallel with the
filter inductor* (the standard Erickson R-L parallel damping — chosen
because the paper states the damping circuit "is inactive when the rack
power is steady": at DC the leg sits across a shorted inductor, carries the
inductor's DC split but dissipates ~nothing, and only absorbs energy during
transients near the LC resonance):

    busbar --+--[L_F]--------+----+---> node (DC-DC input)
             |               |    |
             +--[R_Da+L_Da]--+  [C_F]
                                  |
                                 gnd

States  x = [i_L, v_C, i_D]  (filter-inductor current, capacitor voltage,
damping-leg current).  Inputs u = [v_in, i_load] where ``i_load`` is the
current drawn at the node by the DC-DC stage (rack + battery branch).
The grid-side observable is the busbar current ``i_L + i_D``.

Continuous dynamics (KCL/KVL):

    L_F  di_L/dt = v_in - v_C
    L_Da di_D/dt = v_in - v_C - R_Da i_D
    C_F  dv_C/dt = i_L + i_D - i_load

This is linear, so we discretize **exactly** under a zero-order hold using
the augmented matrix exponential, preserving the paper's "filters behave
exactly as designed" property at any sample rate.  The transfer function
from rack current to grid current,

    H(s) = (i_L + i_D)(s) / i_load(s)   (v_in held fixed),

is second-order with cutoff f_f ~= 1/(2*pi*sqrt(L_F C_F)) and rolls off at
-40 dB/decade (factor 100 per 10x in frequency), matching paper §5.4.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from repro.utils import pytree_dataclass, static_field


@pytree_dataclass
class LCFilterParams:
    """Component values for the input filter (SI units)."""

    l_f: jax.Array  # filter inductance [H]
    c_f: jax.Array  # filter capacitance [F]
    r_da: jax.Array  # damping resistance [Ohm]
    l_da: jax.Array  # damping inductance [H]

    @staticmethod
    def create(l_f: float, c_f: float, r_da: float, l_da: float) -> "LCFilterParams":
        return LCFilterParams(
            l_f=jnp.asarray(l_f, jnp.float32),
            c_f=jnp.asarray(c_f, jnp.float32),
            r_da=jnp.asarray(r_da, jnp.float32),
            l_da=jnp.asarray(l_da, jnp.float32),
        )

    def cutoff_hz(self) -> jax.Array:
        return 1.0 / (2.0 * jnp.pi * jnp.sqrt(self.l_f * self.c_f))


def continuous_abc(p: LCFilterParams):
    """(A, B, C) continuous state-space matrices as numpy (for exactness)."""
    l_f = float(p.l_f)
    c_f = float(p.c_f)
    r_da = float(p.r_da)
    l_da = float(p.l_da)
    a = np.array(
        [
            [0.0, -1.0 / l_f, 0.0],
            [1.0 / c_f, 0.0, 1.0 / c_f],
            [0.0, -1.0 / l_da, -r_da / l_da],
        ]
    )
    b = np.array(
        [
            [1.0 / l_f, 0.0],
            [0.0, -1.0 / c_f],
            [1.0 / l_da, 0.0],
        ]
    )
    c = np.array([[1.0, 0.0, 1.0]])  # observe grid-side current i_L + i_D
    return a, b, c


def discretize_zoh(a: np.ndarray, b: np.ndarray, dt: float):
    """Exact zero-order-hold discretization via the augmented exponential.

    expm([[A, B], [0, 0]] * dt) = [[Ad, Bd], [0, I]].
    """
    n, m = b.shape
    aug = np.zeros((n + m, n + m))
    aug[:n, :n] = a
    aug[:n, n:] = b
    # scipy-free matrix exponential (Pade via jax, evaluated in fp64 numpy).
    import scipy.linalg  # available in this environment

    e = scipy.linalg.expm(aug * dt)
    ad = e[:n, :n]
    bd = e[:n, n:]
    return ad, bd


@pytree_dataclass
class DiscreteFilter:
    """x[t+1] = Ad x[t] + Bd u[t];  y[t] = C x[t] (+ D u[t])."""

    ad: jax.Array  # (n, n)
    bd: jax.Array  # (n, m)
    c: jax.Array  # (p, n)
    dt: float = static_field()


def make_discrete_filter(p: LCFilterParams, dt: float) -> DiscreteFilter:
    a, b, c = continuous_abc(p)
    ad, bd = discretize_zoh(a, b, dt)
    return DiscreteFilter(
        ad=jnp.asarray(ad, jnp.float32),
        bd=jnp.asarray(bd, jnp.float32),
        c=jnp.asarray(c, jnp.float32),
        dt=float(dt),
    )


def steady_state(filt: DiscreteFilter, u: jax.Array) -> jax.Array:
    """State for a constant input u (solves (I - Ad) x = Bd u).

    ``Bd u`` at full f32 precision: a TPU's default would round u to bf16,
    and the conditioned trace would start ~1e-3 pu off its steady state."""
    n = filt.ad.shape[0]
    bu = jnp.matmul(filt.bd, u, precision=jax.lax.Precision.HIGHEST)
    return jnp.linalg.solve(jnp.eye(n) - filt.ad, bu)


def simulate(
    filt: DiscreteFilter,
    x0: jax.Array,
    u: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """Run the filter over inputs ``u``.

    Args:
      filt: discretized filter.
      x0:   initial state, shape (..., n) — leading dims broadcast over racks.
      u:    inputs, shape (T, ..., m).

    Returns:
      (y, x_final): outputs (T, ..., p) and final state (..., n).
    """

    def step(x, u_t):
        x_next = x @ filt.ad.T + u_t @ filt.bd.T
        y_t = x @ filt.c.T
        return x_next, y_t

    x_final, y = jax.lax.scan(step, x0, u)
    return y, x_final


def transfer_function_rack_to_grid(p: LCFilterParams, f_hz: jax.Array) -> jax.Array:
    """|H(j*2*pi*f)| from rack (node) current to grid current.

    Derived from the continuous system with v_in fixed (small-signal):
        H(s) = Z_C(s) / (Z_C(s) + Z_series(s))
    where Z_C = 1/(sC_F) and Z_series = sL_F || (R_Da + sL_Da).
    """
    s = 2j * jnp.pi * f_hz
    z_c = 1.0 / (s * p.c_f)
    z_lf = s * p.l_f
    z_d = p.r_da + s * p.l_da
    z_series = z_lf * z_d / (z_lf + z_d)
    h = z_c / (z_c + z_series)
    return jnp.abs(h)


def resonance_peak_db(p: LCFilterParams, n_points: int = 2048) -> jax.Array:
    """Worst-case magnification (dB) of the damped filter near resonance."""
    f0 = p.cutoff_hz()
    f = jnp.logspace(jnp.log10(f0 / 30.0), jnp.log10(f0 * 30.0), n_points)
    mag = transfer_function_rack_to_grid(p, f)
    return 20.0 * jnp.log10(jnp.max(mag))
