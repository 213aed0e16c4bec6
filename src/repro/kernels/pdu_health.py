"""Interval-resident conditioning megakernel (Pallas TPU).

One launch conditions an entire controller interval: the fused PDU
hardware path of ``pdu_sim`` (ESS ramp filter -> SoC integration -> LC
filter) **plus** the corrective-command slew and the battery-health fold
that previously ran as separate passes around it.  The full per-rack
state — ESS filter value ``g``, SoC, the 3-vector LC state, the
per-sample fault/degraded weight path, and the battery-wear turning-point
machine (previous sample, last extremum, direction, half-cycle count,
cycle damage, max DoD) — stays resident in VMEM for the whole interval,
so the rack trace is read from HBM exactly once per sample and no
intermediate (T, R) block (the slewed corrective profile, the wear
machine's delta stream) round-trips through HBM at all.

Layout: racks tile across lanes (grid = rack tiles of ``r_blk`` lanes;
one grid step owns its tile end-to-end), time rides the sublane axis with
the whole interval resident per tile.  VMEM budget per tile at the fleet
design point (T = 1000 samples, r_blk = 128 lanes, fp32): trace in +
grid/SoC out = 3 x T x r_blk x 4 B = 1.5 MB, plus (5 + 2x6 + 5) x r_blk
x 4 B < 12 KB of state — ~1.5 MB single-buffered (~3 MB with the
pipeline's double buffering, and +0.5 MB each for an optional per-sample
weight or dense corrective operand), comfortably inside the ~16 MB/core
VMEM.  Per lane that is ~12 KB of streaming buffer and 88 B of carried
state — the PR-5 "14-carry spill" was an XLA:CPU *register/L1* pathology
of one wide scan body; here the carries are explicit VMEM rows and never
touch the stack.

Bitwise contract (the PR-5 reproducibility contract, verified in
``tests/test_pdu_health_kernel.py`` against ``ref.pdu_health_sim`` in
interpret mode): the SoC path, the ESS filter value, and every health
leaf are bit-identical to the reference — the turning-point machine
folds sample-by-sample in the step loop (bit-identical under any stream
split), and the throughput / SoC-stress accumulators are whole-interval
``jnp.sum`` reductions evaluated in the wrapper's epilogue over the
kernel's bitwise SoC output, at the exact (t, r) reduce shape the
reference uses — the same single-block reduction, NOT per-sample
accumulator carries or padded-tile reductions (both change the reduction
order; the latter was measured 1 ulp off at narrow widths).  The grid
output and LC filter state agree to a few ulp rather than bitwise: the
LC update is a mul-add chain and XLA contracts it into FMAs differently
across the two loop structures (measured ~4e-7 max on O(1) outputs, a
handful of lanes) — evaluation-order source parity cannot pin that down,
and nothing downstream keys on grid bits (campus aggregation is
tolerance-checked).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _megakernel(
    *refs,
    t_total: int,
    dt: float,
    q_max: float,
    eta_c: float,
    eta_d: float,
    p_max: float,
    soc_min: float,
    soc_max: float,
    masked: bool,
    mask_2d: bool,
    events: bool,
    ess_edge: int,
    slew: bool,
    track_health: bool,
    hconsts: tuple | None,
):
    it = iter(refs)
    ad_ref, bd_ref, c_ref, al_ref, s0_ref, r_ref, corr_ref = (
        next(it) for _ in range(7)
    )
    on_ref = next(it) if (masked and not events) else None
    if events:
        ev_st_ref, ev_en_ref, base_ref, iev_ref = (next(it) for _ in range(4))
    h0_ref = next(it) if track_health else None
    grid_ref, soc_ref, sf_ref = (next(it) for _ in range(3))
    hf_ref = next(it) if track_health else None

    a = ad_ref[...]
    b = bd_ref[...]
    c = c_ref[...]
    alpha = al_ref[0, 0]
    w_row = on_ref[0, :] if (masked and not mask_2d and not events) else None
    if events:
        # Compact episode-table operand: (E, r_blk) sorted int32 boundary
        # tables + a (r_blk,) base availability row, resident in VMEM for
        # the whole interval — replaces the streamed (T, r_blk) weight
        # block (HBM traffic O(E + 1) rows instead of O(T)).
        ev_st = ev_st_ref[...]
        ev_en = ev_en_ref[...]
        ev_base = base_ref[0, :]
        ev_i0 = iev_ref[0, 0]
        ev_tlast = iev_ref[0, 1]
    if slew:
        applied = corr_ref[0, :]
        diff = corr_ref[1, :]
    if track_health:
        c0, c1, eps, kappa = hconsts

    def events_weight(t):
        # Per-step ESS availability from boundary events, the identical
        # clip/where arithmetic as faults.ess_weight (rows sorted, so
        # "entry j <= idx" == "count >= j+1" — same boundary selection as
        # faults._select_boundaries, bitwise).  Clamping the absolute
        # index to the last real sample replicates the streamed path's
        # zero-order-hold repeat-padding.
        idx_t = jnp.minimum(ev_i0 + t, ev_tlast)
        started = [ev_st[j, :] <= idx_t for j in range(ev_st.shape[0])]
        if ess_edge <= 1:
            s_cnt = sum(s.astype(jnp.int32) for s in started)
            e_cnt = sum(
                (ev_en[j, :] <= idx_t).astype(jnp.int32)
                for j in range(ev_en.shape[0])
            )
            intensity = ((s_cnt - e_cnt) > 0).astype(jnp.float32)
        else:
            inv = 1.0 / float(ess_edge)
            st_sel, en_sel = ev_st[0, :], ev_en[0, :]
            for j in range(1, ev_st.shape[0]):
                st_sel = jnp.where(started[j], ev_st[j, :], st_sel)
                en_sel = jnp.where(started[j], ev_en[j, :], en_sel)
            wa = (idx_t - st_sel).astype(jnp.float32)
            wb = (idx_t - en_sel).astype(jnp.float32)
            w = jnp.clip((wa + 1.0) * inv, 0.0, 1.0) - jnp.clip(
                (wb + 1.0) * inv, 0.0, 1.0
            )
            intensity = jnp.where(started[0], w, 0.0)
        return (1.0 - intensity) * ev_base

    def step(t, carry):
        g, soc, x0, x1, x2, hm = carry
        r_t = r_ref[t, :]
        if slew:
            # ramp = (t+1)/T, the identical fused expression the reference
            # evaluates from its arange — the slewed corrective profile is
            # rendered in-register instead of streamed from HBM.
            c_t = applied + diff * ((t + 1).astype(jnp.float32) / t_total)
        else:
            c_t = corr_ref[t, :]
        if masked:
            if events:
                w_t = events_weight(t)
            else:
                w_t = on_ref[t, :] if mask_2d else w_row
        # --- ESS ramp control (paper Eq. 2, exact ZOH) --------------------
        g_new = g + alpha * (r_t - g)
        if masked:
            g_new = jnp.where(w_t > 0, g_new, r_t)
        p_batt = jnp.clip(g_new - r_t + c_t, -p_max, p_max)
        if masked:
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
            soc_new = jnp.where(w_t > 0, soc_new, soc)
        node = r_t + p_batt
        # --- LC filter (grid current out, state update) --------------------
        grid_ref[t, :] = (c[0, 0] * x0 + c[0, 1] * x1 + c[0, 2] * x2).astype(
            grid_ref.dtype
        )
        soc_ref[t, :] = soc_new
        x0n = a[0, 0] * x0 + a[0, 1] * x1 + a[0, 2] * x2 + b[0, 1] * node + b[0, 0]
        x1n = a[1, 0] * x0 + a[1, 1] * x1 + a[1, 2] * x2 + b[1, 1] * node + b[1, 0]
        x2n = a[2, 0] * x0 + a[2, 1] * x1 + a[2, 2] * x2 + b[2, 1] * node + b[2, 0]
        # --- wear turning-point machine (core.health semantics) ------------
        if track_health:
            prev, last_ext, dirn, half, dmg_acc, mdod = hm
            # prev is the wear stream's previous sample (seeded from the
            # health state, == the ESS carry thereafter), so delta matches
            # the reference's prev_soc-relative first step by construction.
            delta = soc_new - prev
            sd = jnp.where(delta > eps, 1.0, jnp.where(delta < -eps, -1.0, 0.0))
            rev = (sd * dirn) < 0.0
            revf = jnp.where(rev, 1.0, 0.0)
            depth = jnp.abs(prev - last_ext)
            half_w = jnp.maximum(c0 + c1 * (prev + last_ext), 0.0)
            if float(kappa) == 1.0:
                powd = depth
            elif float(kappa).is_integer() and 2 <= int(kappa) <= 4:
                powd = depth
                for _ in range(int(kappa) - 1):
                    powd = powd * depth
            else:
                powd = jnp.power(depth, kappa)
            hm = (
                soc_new,
                jnp.where(rev, prev, last_ext),
                jnp.where(sd != 0.0, sd, dirn),
                half + revf,
                dmg_acc + revf * (half_w * powd),
                jnp.maximum(mdod, revf * depth),
            )
        return (g_new, soc_new, x0n, x1n, x2n, hm)

    hm0 = tuple(h0_ref[i, :] for i in range(6)) if track_health else ()
    carry0 = (s0_ref[0, :], s0_ref[1, :], s0_ref[2, :], s0_ref[3, :], s0_ref[4, :], hm0)
    g, soc, x0, x1, x2, hm = jax.lax.fori_loop(0, t_total, step, carry0)
    sf_ref[...] = jnp.stack([g, soc, x0, x1, x2], axis=0)
    if track_health:
        hf_ref[...] = jnp.stack([hm[0], hm[1], hm[2], hm[3], hm[4], hm[5]], axis=0)


@functools.partial(
    jax.jit,
    static_argnames=(
        "beta", "dt", "q_max", "eta_c", "eta_d", "p_max", "soc_min", "soc_max",
        "health_consts", "ess_edge", "r_blk", "interpret",
    ),
)
def pdu_health_sim(
    rack_power: jax.Array,  # (T, R)
    g0: jax.Array,  # (R,)
    soc0: jax.Array,  # (R,)
    x0: jax.Array,  # (R, 3)
    ad: jax.Array,
    bd: jax.Array,
    c_row: jax.Array,
    *,
    beta: float,
    dt: float,
    q_max: float,
    eta_c: float,
    eta_d: float,
    p_max: float,
    soc_min: float,
    soc_max: float,
    corrective: jax.Array | float = 0.0,
    slew: tuple[jax.Array, jax.Array] | None = None,
    ess_on: jax.Array | None = None,
    ess_events: tuple | None = None,  # (starts, ends, base, i0, t_last)
    ess_edge: int = 1,
    health_consts: tuple | None = None,  # (c0, c1, eps, kappa) host floats
    health_state: tuple | None = None,  # 11 HealthState leaves, (R,) each
    r_blk: int = 128,
    interpret: bool = False,
):
    """Interval-resident megakernel.  Same contract as ``ref.pdu_health_sim``
    (health passed as the split ``health_consts`` / ``health_state`` so the
    consts stay static; ``ess_events``/``ess_edge`` render the per-sample
    availability weight in-kernel from sorted (E, R) boundary tables, see
    the reference docstring).  Returns
    ``(grid (T,R), soc (T,R), (g_f, soc_f, x_f), health_leaves_or_None)``.
    """
    t, r = rack_power.shape
    track_health = health_state is not None
    events = ess_events is not None
    if events and ess_on is not None:
        raise ValueError("pass either ess_on or ess_events, not both")
    masked = ess_on is not None or events
    mask_2d = ess_on is not None and ess_on.ndim == 2
    r_pad = -r % r_blk
    rp_w = r + r_pad
    t_pad = -t % 8  # sublane-align the time axis; the loop stops at t
    f32 = jnp.float32

    def pad_tr(x):  # (T, R) operand -> (T + t_pad, R + r_pad)
        x = x.astype(f32)
        if r_pad:
            x = jnp.pad(x, ((0, 0), (0, r_pad)))
        if t_pad:
            x = jnp.pad(x, ((0, t_pad), (0, 0)))
        return x

    def pad_r(x):  # (R,) row -> (R + r_pad,)
        x = jnp.broadcast_to(x, (r,)).astype(f32)
        return jnp.pad(x, (0, r_pad)) if r_pad else x

    # alpha is traced with the exact expression the reference evaluates —
    # a 1-ulp difference (e.g. from host-side float64 exp) shows up as ulp
    # drift across the whole grid/LC path.
    alpha = (1.0 - jnp.exp(-jnp.asarray(beta, jnp.float32) * dt)).reshape(1, 1)
    s0 = jnp.stack([pad_r(g0), pad_r(soc0)] + [pad_r(x0[:, i]) for i in range(3)])
    const_specs = [
        pl.BlockSpec((3, 3), lambda i: (0, 0)),
        pl.BlockSpec((3, 2), lambda i: (0, 0)),
        pl.BlockSpec((1, 3), lambda i: (0, 0)),
        pl.BlockSpec((1, 1), lambda i: (0, 0)),
    ]
    operands = [ad.astype(f32), bd.astype(f32), c_row.reshape(1, 3).astype(f32), alpha]
    in_specs = const_specs + [
        pl.BlockSpec((5, r_blk), lambda i: (0, i)),
        pl.BlockSpec((t + t_pad, r_blk), lambda i: (0, i)),
    ]
    operands += [s0, pad_tr(rack_power)]
    if slew is not None:
        applied, target = slew
        applied = pad_r(applied)
        corr_op = jnp.stack([applied, pad_r(target) - applied], axis=0)  # (2, Rp)
        in_specs.append(pl.BlockSpec((2, r_blk), lambda i: (0, i)))
    else:
        corr_op = pad_tr(jnp.broadcast_to(jnp.asarray(corrective, f32), (t, r)))
        in_specs.append(pl.BlockSpec((t + t_pad, r_blk), lambda i: (0, i)))
    operands.append(corr_op)
    if mask_2d:
        in_specs.append(pl.BlockSpec((t + t_pad, r_blk), lambda i: (0, i)))
        operands.append(pad_tr(ess_on))
    elif masked and not events:
        in_specs.append(pl.BlockSpec((1, r_blk), lambda i: (0, i)))
        operands.append(pad_r(ess_on).reshape(1, rp_w))
    if events:
        ev_st, ev_en, ev_base, ev_i0, ev_tlast = ess_events

        def pad_ri(x):  # (E, R) int32 table -> (E, R + r_pad), pad = never
            x = jnp.asarray(x, jnp.int32)
            if r_pad:
                x = jnp.pad(
                    x, ((0, 0), (0, r_pad)),
                    constant_values=jnp.iinfo(jnp.int32).max,
                )
            return x

        n_ev = ev_st.shape[0]
        in_specs += [
            pl.BlockSpec((n_ev, r_blk), lambda i: (0, i)),
            pl.BlockSpec((n_ev, r_blk), lambda i: (0, i)),
            pl.BlockSpec((1, r_blk), lambda i: (0, i)),
            pl.BlockSpec((1, 2), lambda i: (0, 0)),
        ]
        operands += [
            pad_ri(ev_st),
            pad_ri(ev_en),
            pad_r(ev_base).reshape(1, rp_w),
            jnp.stack(
                [jnp.asarray(ev_i0, jnp.int32), jnp.asarray(ev_tlast, jnp.int32)]
            ).reshape(1, 2),
        ]
    if track_health:
        h0 = jnp.stack([pad_r(l) for l in health_state[:6]], axis=0)  # (6, Rp)
        in_specs.append(pl.BlockSpec((6, r_blk), lambda i: (0, i)))
        operands.append(h0)

    out_specs = [
        pl.BlockSpec((t + t_pad, r_blk), lambda i: (0, i)),
        pl.BlockSpec((t + t_pad, r_blk), lambda i: (0, i)),
        pl.BlockSpec((5, r_blk), lambda i: (0, i)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((t + t_pad, rp_w), rack_power.dtype),
        jax.ShapeDtypeStruct((t + t_pad, rp_w), f32),
        jax.ShapeDtypeStruct((5, rp_w), f32),
    ]
    if track_health:
        out_specs.append(pl.BlockSpec((6, r_blk), lambda i: (0, i)))
        out_shape.append(jax.ShapeDtypeStruct((6, rp_w), f32))

    outs = pl.pallas_call(
        functools.partial(
            _megakernel,
            t_total=t, dt=dt, q_max=q_max, eta_c=eta_c,
            eta_d=eta_d, p_max=p_max, soc_min=soc_min, soc_max=soc_max,
            masked=masked, mask_2d=mask_2d, events=events, ess_edge=ess_edge,
            slew=slew is not None,
            track_health=track_health, hconsts=health_consts,
        ),
        grid=(rp_w // r_blk,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*operands)
    grid_t, soc_t, sf = outs[0][:t, :r], outs[1][:t, :r], outs[2][:, :r]
    finals = (sf[0], sf[1], sf[2:5].T)
    if not track_health:
        return grid_t, soc_t, finals, None
    hf = outs[3][:, :r]
    # Block accumulators: the reference's whole-interval reductions,
    # verbatim, over the sliced (t, r) SoC path — deliberately OUTSIDE the
    # kernel so the reduce shape (and therefore XLA's accumulator
    # splitting) matches the reference for every fleet width; reducing the
    # padded (t, r_blk) tile in-kernel reassociates by 1 ulp at narrow
    # widths.  XLA fuses this epilogue with the kernel's soc_t output.
    prev_soc = jnp.broadcast_to(health_state[0], (r,)).astype(f32)
    prev_t = jnp.concatenate(
        [jnp.broadcast_to(prev_soc, soc_t[:1].shape), soc_t[:-1]], axis=0
    )
    delta = soc_t - prev_t
    h_out = tuple(hf[i] for i in range(6)) + (
        health_state[6] + jnp.sum(jnp.maximum(delta, 0.0), axis=0),
        health_state[7] + jnp.sum(jnp.maximum(-delta, 0.0), axis=0),
        health_state[8] + jnp.sum(soc_t, axis=0),
        health_state[9] + jnp.sum(soc_t * soc_t, axis=0),
        jnp.broadcast_to(health_state[10], (r,)) + jnp.int32(t),
    )
    return grid_t, soc_t, finals, h_out
