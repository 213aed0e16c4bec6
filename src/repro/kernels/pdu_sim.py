"""Fused EasyRider PDU hardware path as a single Pallas TPU kernel.

Beyond-paper optimization: the reference pipeline makes three passes over
the trace (ESS ramp filter -> SoC integration -> LC filter), each reading
and writing HBM.  Fusing them keeps the full per-rack state — ESS filter
value g, state of charge, and the 3-vector LC state — resident in VMEM and
makes exactly one HBM read (rack trace + corrective) and two writes (grid
trace, SoC telemetry) per sample.  Arithmetic intensity triples and the
power-sim roofline moves from memory-bound toward compute-bound (see
EXPERIMENTS.md §Perf).

Layout identical to ``lc_filter``: racks in lanes, time blocked, state in
persistent VMEM scratch (5 rows: g, soc, x0, x1, x2).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _pdu_kernel(
    *refs,
    block_t: int,
    t_total: int,
    alpha: float,
    dt: float,
    q_max: float,
    eta_c: float,
    eta_d: float,
    p_max: float,
    soc_min: float,
    soc_max: float,
    masked: bool,
    mask_2d: bool = False,
):
    if masked:
        (ad_ref, bd_ref, c_ref, s0_ref, r_ref, corr_ref, on_ref,
         grid_ref, soc_ref, sf_ref, state) = refs
        w_row = None if mask_2d else on_ref[0, :]
    else:
        (ad_ref, bd_ref, c_ref, s0_ref, r_ref, corr_ref,
         grid_ref, soc_ref, sf_ref, state) = refs

    @pl.when(pl.program_id(0) == 0)
    def _init():
        state[...] = s0_ref[...]

    n_valid = jnp.minimum(block_t, t_total - pl.program_id(0) * block_t)

    a = ad_ref[...]
    b = bd_ref[...]
    c = c_ref[...]

    def step(t, s):
        g, soc, x0, x1, x2 = s[0], s[1], s[2], s[3], s[4]
        r_t = r_ref[t, :]
        c_t = corr_ref[t, :]
        if masked:
            w_t = on_ref[t, :] if mask_2d else w_row
        # --- ESS ramp control (paper Eq. 2, exact ZOH) --------------------
        g_new = g + alpha * (r_t - g)
        if masked:
            # Offline units track the rack (soft re-engage on recovery).
            g_new = jnp.where(w_t > 0, g_new, r_t)
        p_batt = jnp.clip(g_new - r_t + c_t, -p_max, p_max)
        if masked:
            # Converter wind-down: deliver the weighted fraction (w = 1 is
            # an exact multiply; w = 0 is the hard passthrough, bitwise).
            p_batt = p_batt * w_t
        # --- SoC integration with efficiency asymmetry (Eq. 14) -----------
        charge = jnp.maximum(p_batt, 0.0)
        discharge = jnp.maximum(-p_batt, 0.0)
        soc_new = soc + (dt / q_max) * (eta_c * charge - discharge / eta_d)
        over_hi = jnp.maximum(soc_new - soc_max, 0.0)
        over_lo = jnp.maximum(soc_min - soc_new, 0.0)
        p_batt = p_batt - over_hi * q_max / (eta_c * dt) + over_lo * q_max * eta_d / dt
        soc_new = jnp.clip(soc_new, soc_min, soc_max)
        if masked:
            # LC passthrough: SoC frozen while the unit is dark.
            soc_new = jnp.where(w_t > 0, soc_new, soc)
        node = r_t + p_batt
        # --- LC filter (grid current out, state update) --------------------
        grid_ref[t, :] = (c[0, 0] * x0 + c[0, 1] * x1 + c[0, 2] * x2).astype(
            grid_ref.dtype
        )
        soc_ref[t, :] = soc_new.astype(soc_ref.dtype)
        x0n = a[0, 0] * x0 + a[0, 1] * x1 + a[0, 2] * x2 + b[0, 1] * node + b[0, 0]
        x1n = a[1, 0] * x0 + a[1, 1] * x1 + a[1, 2] * x2 + b[1, 1] * node + b[1, 0]
        x2n = a[2, 0] * x0 + a[2, 1] * x1 + a[2, 2] * x2 + b[2, 1] * node + b[2, 0]
        return jnp.stack([g_new, soc_new, x0n, x1n, x2n], axis=0)

    state[...] = jax.lax.fori_loop(0, n_valid, step, state[...])
    sf_ref[...] = state[...]


@functools.partial(
    jax.jit,
    static_argnames=(
        "beta", "dt", "q_max", "eta_c", "eta_d", "p_max", "soc_min", "soc_max",
        "block_t", "interpret",
    ),
)
def pdu_sim(
    rack_power: jax.Array,  # (T, R)
    g0: jax.Array,  # (R,)
    soc0: jax.Array,  # (R,)
    x0: jax.Array,  # (R, 3)
    ad: jax.Array,
    bd: jax.Array,
    c_row: jax.Array,
    corrective: jax.Array,  # (T, R)
    *,
    beta: float,
    dt: float,
    q_max: float,
    eta_c: float,
    eta_d: float,
    p_max: float,
    soc_min: float,
    soc_max: float,
    block_t: int = 512,
    interpret: bool = False,
    ess_on: jax.Array | None = None,  # (R,) or (T, R) availability weight
) -> tuple[jax.Array, jax.Array, tuple[jax.Array, jax.Array, jax.Array]]:
    """Fused hardware-path sim.  Returns (grid (T,R), soc (T,R), finals).

    ``ess_on`` (degraded mode) is an availability weight in [0, 1] — a
    ``(R,)`` row or a ``(T, R)`` per-sample series — see ``ref.pdu_sim``
    for the exact semantics; both paths match bitwise.
    """
    import math

    t, r = rack_power.shape
    masked = ess_on is not None
    mask_2d = masked and ess_on.ndim == 2
    block_t = min(block_t, t)
    pad_t = -t % block_t
    rp = rack_power.astype(jnp.float32)
    cp = corrective.astype(jnp.float32)
    if pad_t:
        rp = jnp.concatenate([rp, jnp.tile(rp[-1:], (pad_t, 1))], axis=0)
        cp = jnp.concatenate([cp, jnp.tile(cp[-1:], (pad_t, 1))], axis=0)
    s0 = jnp.stack(
        [g0.astype(jnp.float32), soc0.astype(jnp.float32)]
        + [x0[:, i].astype(jnp.float32) for i in range(3)],
        axis=0,
    )  # (5, R)
    grid = ((t + pad_t) // block_t,)
    alpha = 1.0 - math.exp(-beta * dt)
    in_specs = [
        pl.BlockSpec((3, 3), lambda i: (0, 0)),
        pl.BlockSpec((3, 2), lambda i: (0, 0)),
        pl.BlockSpec((1, 3), lambda i: (0, 0)),
        pl.BlockSpec((5, r), lambda i: (0, 0)),
        pl.BlockSpec((block_t, r), lambda i: (i, 0)),
        pl.BlockSpec((block_t, r), lambda i: (i, 0)),
    ]
    operands = [
        ad.astype(jnp.float32),
        bd.astype(jnp.float32),
        c_row.reshape(1, 3).astype(jnp.float32),
        s0,
        rp,
        cp,
    ]
    if mask_2d:
        wp = ess_on.astype(jnp.float32)
        if pad_t:
            wp = jnp.concatenate([wp, jnp.tile(wp[-1:], (pad_t, 1))], axis=0)
        in_specs.append(pl.BlockSpec((block_t, r), lambda i: (i, 0)))
        operands.append(wp)
    elif masked:
        in_specs.append(pl.BlockSpec((1, r), lambda i: (0, 0)))
        operands.append(ess_on.reshape(1, r).astype(jnp.float32))
    y, soc_t, sf = pl.pallas_call(
        functools.partial(
            _pdu_kernel,
            block_t=block_t, t_total=t, alpha=alpha, dt=dt, q_max=q_max,
            eta_c=eta_c, eta_d=eta_d, p_max=p_max, soc_min=soc_min,
            soc_max=soc_max, masked=masked, mask_2d=mask_2d,
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((block_t, r), lambda i: (i, 0)),
            pl.BlockSpec((block_t, r), lambda i: (i, 0)),
            pl.BlockSpec((5, r), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t + pad_t, r), rack_power.dtype),
            jax.ShapeDtypeStruct((t + pad_t, r), jnp.float32),
            jax.ShapeDtypeStruct((5, r), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((5, r), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*operands)
    g_f, soc_f, x_f = sf[0], sf[1], sf[2:5].T
    return y[:t], soc_t[:t], (g_f, soc_f, x_f)
