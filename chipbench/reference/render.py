"""Rack power traces of a parametric campus, sample by absolute index.

Per rack r and time t = i * dt (job-local time te = t - t_start):

* iteration wave: p_comm in the last ``comm_fraction`` of each
  ``iteration_period_s``, p_compute otherwise; a checkpoint stall (p_dip)
  for the first ``dip_duration_s`` of each ``dip_period_s``;
* warm-up: p = p_idle + clip(te / warmup, 0, 1) (p - p_idle);
* diurnal envelope (amp > 0): p = p_idle + env (p - p_idle) with
  env = 1 - amp (1 - cos(2 pi (t - phase) / period)) / 2;
* idle before the start and from ``t_end_s`` on;
* edges smoothed by a ``w``-sample boxcar over [i - (w - 1 - c), i + c],
  c = (w - 1) // 2, the index clamped to the trace ("clamp" padding);
* a scripted fault window sets p_fault, unsmoothed;
* measurement noise noise_std * z, z standard normal from a murmur3
  counter hash of (noise seed, sample, rack, salt) through the inverse
  normal CDF, then clip to [0, 1] and the per-rack scale.

The noise hash is part of the data: the program draws the same numbers.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

NEVER = 1e30


def _fmix32(x):
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def noise(seed: int, idx, n_racks: int, salt):
    """(n, R) standard normal draws for absolute sample indices ``idx``."""
    lane = (jnp.arange(n_racks, dtype=jnp.uint32) * jnp.uint32(0x9E3779B9)
            ^ (jnp.uint32(seed) * jnp.uint32(0x85EBCA6B) + jnp.uint32(0x2545F491)))
    lane = _fmix32(lane ^ jnp.asarray(salt, jnp.uint32))
    h = _fmix32(idx.astype(jnp.uint32)[:, None] ^ lane[None, :])
    u = (h >> jnp.uint32(8)).astype(jnp.float32) * jnp.float32(2.0 ** -24)
    u = u + jnp.float32(2.0 ** -25)
    return jnp.float32(np.sqrt(2.0)) * jax.scipy.special.erfinv(2.0 * u - 1.0)


def _two_prod_err(a, b):
    """a * b - fl(a * b), exactly (Dekker's split, no fused multiply-add)."""
    split = jnp.float32(4097.0)
    ca, cb = split * a, split * b
    a_hi = ca - (ca - a)
    b_hi = cb - (cb - b)
    a_lo, b_lo = a - a_hi, b - b_hi
    p = a * b
    return ((a_hi * b_hi - p) + a_hi * b_lo + a_lo * b_hi) + a_lo * b_lo, p


def floor_mod(x, y):
    """numpy's ``mod`` for y > 0: the exact C remainder fmod(x, y), plus y
    (one rounding) where it is negative.  A TPU's float division and
    remainder are not correctly rounded, so the truncated quotient is
    corrected by one step from an exact remainder (the C remainder of two
    floats is itself a float, and x - q y is formed without rounding)."""
    def rem(q):
        err, p = _two_prod_err(q, y)
        return (x - p) - err

    one = lambda c: c.astype(x.dtype)
    q = jnp.trunc(x / y)
    r = rem(q)
    q = q + jnp.where(x >= 0, one(r >= y) - one(r < 0), one(r > 0) - one(r <= -y))
    r = rem(q)
    return jnp.where(r < 0, r + y, r)


def base(cols: dict, t, dt: float):
    """Unsmoothed power at times ``t`` (n,) for per-rack knobs (R,)."""
    t = t[:, None]
    te = t - cols["t_start_s"]
    period = cols["iteration_period_s"]
    phase = floor_mod(te, period) / period
    p = jnp.where(phase >= 1.0 - cols["comm_fraction"], cols["p_comm"], cols["p_compute"])
    in_dip = (floor_mod(te, cols["dip_period_s"]) < cols["dip_duration_s"]) & (
        cols["dip_period_s"] < 0.5 * NEVER)
    p = jnp.where(in_dip, cols["p_dip"], p)
    ramp = jnp.clip(te / jnp.maximum(cols["warmup_s"], dt), 0.0, 1.0)
    p = cols["p_idle"] + ramp * (p - cols["p_idle"])
    period = jnp.maximum(cols["diurnal_period_s"], dt)
    env = 1.0 - cols["diurnal_amp"] * 0.5 * (
        1.0 - jnp.cos(2.0 * jnp.pi * (t - cols["diurnal_phase_s"]) / period))
    p = jnp.where(cols["diurnal_amp"] > 0.0, cols["p_idle"] + env * (p - cols["p_idle"]), p)
    return jnp.where((te < 0.0) | (t >= cols["t_end_s"]), cols["p_idle"], p)


def render(cols: dict, salt, t0, n: int, *, dt: float, total: int, w: int,
           pad: str, noise_seed: int):
    """(n, R) float32 rack power for samples [t0, t0 + n)."""
    idx = t0 + jnp.arange(n, dtype=jnp.int32)
    if w > 1:
        c = (w - 1) // 2
        eidx = (t0 - (w - 1 - c)) + jnp.arange(n + w - 1, dtype=jnp.int32)
        if pad != "clamp":
            raise ValueError(f"edge padding {pad!r} is not modelled")
        b = base(cols, jnp.clip(eidx, 0, total - 1).astype(jnp.float32) * dt, dt)
        acc = b[0:n]
        for j in range(1, w):
            acc = acc + b[j:j + n]
        p = acc / w
    else:
        p = base(cols, idx.astype(jnp.float32) * dt, dt)
    t = (idx.astype(jnp.float32) * dt)[:, None]
    in_fault = (t >= cols["fault_at_s"]) & (t < cols["fault_at_s"] + cols["fault_duration_s"])
    p = jnp.where(in_fault, cols["p_fault"], p)
    z = noise(noise_seed, idx, p.shape[1], salt)
    p = jnp.clip(p + cols["noise_std"] * z, 0.0, 1.0)
    return (p * cols["scale"]).astype(jnp.float32)
