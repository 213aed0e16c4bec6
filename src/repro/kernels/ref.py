"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth the kernels are validated against (interpret
mode on CPU, compiled on TPU) and the implementations the public ``ops``
wrappers fall back to on non-TPU backends.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


# ----------------------------------------------------------------- lc_filter


def lc_filter(
    ad: jax.Array,  # (3, 3) discrete state matrix
    bd: jax.Array,  # (3, 2) discrete input matrix
    c_row: jax.Array,  # (3,) output row (grid current)
    x0: jax.Array,  # (R, 3) initial state per rack
    node_power: jax.Array,  # (T, R) per-unit node power (i_load input)
) -> tuple[jax.Array, jax.Array]:
    """State-space IIR filter over a trace; v_in is fixed at 1.0 per-unit.

    Returns (grid (T, R), x_final (R, 3)).
    """
    b_vin = bd[:, 0]  # constant drive from v_in = 1
    b_load = bd[:, 1]

    def step(x, u_t):
        y = x @ c_row
        x_next = x @ ad.T + u_t[:, None] * b_load[None, :] + b_vin[None, :]
        return x_next, y

    x_f, y = jax.lax.scan(step, x0, node_power)
    return y, x_f


# ------------------------------------------------------------------- pdu_sim


def pdu_sim(
    rack_power: jax.Array,  # (T, R)
    g0: jax.Array,  # (R,) ESS filter state
    soc0: jax.Array,  # (R,)
    x0: jax.Array,  # (R, 3) LC filter state
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
    corrective: jax.Array | float = 0.0,  # scalar or (T, R)
    ess_on: jax.Array | None = None,  # (R,) or (T, R) availability weight
) -> tuple[jax.Array, jax.Array, tuple]:
    """Fused EasyRider hardware path: ESS ramp control + SoC + LC filter.

    Semantically identical to ``core.ess.simulate`` piped into
    ``core.filters.simulate``; implemented as a single scan so the fused
    Pallas kernel has a one-pass oracle. Returns (grid (T,R), soc (T,R),
    (g_f, soc_f, x_f)).

    ``ess_on`` is a per-rack ESS availability *weight* in [0, 1] — a
    ``(R,)`` row held for the whole call or a ``(T, R)`` per-sample array.
    Weight 0 puts a rack in LC passthrough (p_batt = 0, SoC frozen, the
    node sees the raw rack power) while the ramp filter keeps *tracking*
    the rack so a recovering unit re-engages softly from g = r rather
    than slamming a stale setpoint.  Fractional weights scale the battery
    power (converter wind-down/soft-start around a trip), with the SoC
    integrating the scaled power.  With ``ess_on=None`` (or all ones) the
    computation is bitwise identical to the unmasked path, and binary
    weights are bitwise identical to the legacy boolean-mask semantics.
    """
    alpha = 1.0 - jnp.exp(-jnp.asarray(beta) * dt)
    corr = jnp.broadcast_to(jnp.asarray(corrective, rack_power.dtype), rack_power.shape)
    masked = ess_on is not None
    w_all = (
        jnp.broadcast_to(ess_on.astype(rack_power.dtype), rack_power.shape)
        if masked
        else None
    )
    # Unpacked state columns + scalar*vector FMAs instead of a per-step
    # (R,3)@(3,3) dot: measured +7% wall clock on host (EXPERIMENTS §Perf-1
    # it.3) and matches the Pallas kernel's formulation exactly.
    a = ad
    bl = bd[:, 1]
    bv = bd[:, 0]

    def step(carry, inp):
        g, soc, s0, s1, s2 = carry
        if masked:
            r_t, c_t, w_t = inp
        else:
            r_t, c_t = inp
        g_new = g + alpha * (r_t - g)
        if masked:
            g_new = jnp.where(w_t > 0, g_new, r_t)
        p_batt = jnp.clip(g_new - r_t + c_t, -p_max, p_max)
        if masked:
            # Converter wind-down: battery delivers the weighted fraction
            # of the commanded power (w = 1 is an exact multiply, w = 0
            # reproduces the hard passthrough bitwise).
            p_batt = p_batt * w_t
        charge = jnp.maximum(p_batt, 0.0)
        discharge = jnp.maximum(-p_batt, 0.0)
        d_soc = (dt / q_max) * (eta_c * charge - discharge / eta_d)
        soc_new = soc + d_soc
        over_hi = jnp.maximum(soc_new - soc_max, 0.0)
        over_lo = jnp.maximum(soc_min - soc_new, 0.0)
        p_batt = p_batt - over_hi * q_max / (eta_c * dt) + over_lo * q_max * eta_d / dt
        soc_new = jnp.clip(soc_new, soc_min, soc_max)
        if masked:
            soc_new = jnp.where(w_t > 0, soc_new, soc)
        node = r_t + p_batt
        y = c_row[0] * s0 + c_row[1] * s1 + c_row[2] * s2
        n0 = a[0, 0] * s0 + a[0, 1] * s1 + a[0, 2] * s2 + bl[0] * node + bv[0]
        n1 = a[1, 0] * s0 + a[1, 1] * s1 + a[1, 2] * s2 + bl[1] * node + bv[1]
        n2 = a[2, 0] * s0 + a[2, 1] * s1 + a[2, 2] * s2 + bl[2] * node + bv[2]
        return (g_new, soc_new, n0, n1, n2), (y, soc_new)

    carry0 = (g0, soc0, x0[:, 0], x0[:, 1], x0[:, 2])
    xs = (rack_power, corr, w_all) if masked else (rack_power, corr)
    (g_f, soc_f, s0, s1, s2), (grid, soc_t) = jax.lax.scan(step, carry0, xs)
    x_f = jnp.stack([s0, s1, s2], axis=-1)
    return grid, soc_t, (g_f, soc_f, x_f)


# ------------------------------------------------------------- pdu_health_sim


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
    corrective: jax.Array | float = 0.0,  # scalar or (T, R)
    slew: tuple[jax.Array, jax.Array] | None = None,  # (applied, target) rows
    ess_on: jax.Array | None = None,  # (R,) or (T, R) availability weight
    ess_events: tuple | None = None,  # (starts, ends, base, i0, t_last)
    ess_edge: int = 1,
    health: tuple | None = None,  # ((c0, c1, eps, kappa), state_leaves)
) -> tuple[jax.Array, jax.Array, tuple, tuple | None]:
    """One-call oracle for the interval-resident conditioning megakernel.

    Extends ``pdu_sim`` with the fusions the megakernel performs per
    controller interval:

    * **In-scan command slew** — ``slew=(applied, target)`` renders the
      corrective-power ramp ``applied + (target - applied) * (t+1)/T``
      per step from two ``(R,)`` rows instead of consuming a materialized
      ``(T, R)`` profile.  Each element evaluates the identical fused
      expression, so the output is bitwise equal to passing the broadcast
      profile via ``corrective`` (and to the pre-fusion pipeline).
    * **Fused health fold** — ``health=(step_consts, state_leaves)`` folds
      the battery-wear telemetry of ``core.health.update_consts`` in the
      same call: the 5-carry turning-point machine rides its own scan and
      the throughput/stress integrals stay whole-interval ``jnp.sum``
      block reductions over the simulated SoC path.  Every leaf is
      bitwise identical to ``update_consts`` on ``pdu_sim``'s SoC output
      (this reference keeps that hybrid formulation verbatim — it is the
      profiled CPU optimum); the Pallas megakernel instead carries the
      previous sample through its single step loop, which evaluates the
      same per-step expressions on the same values and so matches
      bitwise.  Preserving the PR-5 split-invariance contract was the
      design constraint: per-sample accumulator carries and per-block
      partial sums both change the reduction order — measured 1-ulp
      drift — so neither is used anywhere.  ``state_leaves`` is the flat
      ``HealthState`` tuple; the kernels layer stays free of ``core``
      imports.
    * **In-scan ESS weight rendering** — ``ess_events=(starts, ends, base,
      i0, t_last)`` replaces the streamed ``(T, R)`` availability block
      with a compact episode-table operand: sorted ``(E, R)`` int32
      start/end boundary tables (padded with empty intervals), a ``(R,)``
      base availability row (interval online-mask x sensed-mask), and the
      scalar absolute index ``i0`` of the first sample plus ``t_last``,
      the absolute index of the last *real* sample (per-step indices clamp
      to it so zero-order-hold padding replicates the last real weight,
      matching the streamed path's repeat-pad).  Each step renders
      ``w_t = (1 - edge_intensity(idx_t)) * base`` with the identical
      clip/where arithmetic as ``faults.ess_weight`` — the same two float
      ops on the same inputs as the precomputed ``weight * base`` product,
      so the result is bitwise equal to streaming that product via
      ``ess_on``.  ``ess_edge`` is the static wind-down width in samples
      (``<= 1`` renders binary membership exactly).

    Returns ``(grid, soc_t, (g_f, soc_f, x_f), health_leaves_or_None)``.
    """
    alpha = 1.0 - jnp.exp(-jnp.asarray(beta) * dt)
    t = rack_power.shape[0]
    events = ess_events is not None
    if events and ess_on is not None:
        raise ValueError("pass either ess_on or ess_events, not both")
    masked = ess_on is not None or events
    w_all = (
        jnp.broadcast_to(ess_on.astype(rack_power.dtype), rack_power.shape)
        if ess_on is not None
        else None
    )
    if events:
        ev_st, ev_en, ev_base, ev_i0, ev_tlast = ess_events
        ev_st = jnp.asarray(ev_st, jnp.int32)  # (E, R) sorted along axis 0
        ev_en = jnp.asarray(ev_en, jnp.int32)
        idxvec = jnp.minimum(
            jnp.asarray(ev_i0, jnp.int32) + jnp.arange(t, dtype=jnp.int32),
            jnp.asarray(ev_tlast, jnp.int32),
        )

        def events_weight(idx_t):
            # Rows are sorted along the episode axis, so "entry j is
            # at-or-before idx" is exactly "count >= j+1" — the unrolled
            # compares below select the same boundaries (and the same
            # cnt>0 gate) as faults._select_boundaries, bitwise.
            started = [ev_st[j] <= idx_t for j in range(ev_st.shape[0])]
            if ess_edge <= 1:
                s_cnt = sum(s.astype(jnp.int32) for s in started)
                e_cnt = sum(
                    (ev_en[j] <= idx_t).astype(jnp.int32)
                    for j in range(ev_en.shape[0])
                )
                intensity = ((s_cnt - e_cnt) > 0).astype(jnp.float32)
            else:
                inv = 1.0 / float(ess_edge)
                st_sel, en_sel = ev_st[0], ev_en[0]
                for j in range(1, ev_st.shape[0]):
                    st_sel = jnp.where(started[j], ev_st[j], st_sel)
                    en_sel = jnp.where(started[j], ev_en[j], en_sel)
                a = (idx_t - st_sel).astype(jnp.float32)
                b = (idx_t - en_sel).astype(jnp.float32)
                w = jnp.clip((a + 1.0) * inv, 0.0, 1.0) - jnp.clip(
                    (b + 1.0) * inv, 0.0, 1.0
                )
                intensity = jnp.where(started[0], w, 0.0)
            return (1.0 - intensity) * ev_base
    if slew is not None:
        applied, target = slew
        diff = target - applied
        ramp01 = jnp.arange(1, t + 1, dtype=jnp.float32) / t
        corr_parts, corr = (applied, diff, ramp01), None
    else:
        corr = jnp.broadcast_to(
            jnp.asarray(corrective, rack_power.dtype), rack_power.shape
        )
        corr_parts = None
    a = ad
    bl = bd[:, 1]
    bv = bd[:, 0]

    def step(carry, inp):
        g, soc, s0, s1, s2 = carry
        if slew is not None:
            (r_t, ramp_t, *rest) = inp
            c_t = corr_parts[0] + corr_parts[1] * ramp_t
        else:
            (r_t, c_t, *rest) = inp
        if masked:
            w_t = events_weight(rest[0]) if events else rest[0]
        g_new = g + alpha * (r_t - g)
        if masked:
            g_new = jnp.where(w_t > 0, g_new, r_t)
        p_batt = jnp.clip(g_new - r_t + c_t, -p_max, p_max)
        if masked:
            p_batt = p_batt * w_t
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
        y = c_row[0] * s0 + c_row[1] * s1 + c_row[2] * s2
        n0 = a[0, 0] * s0 + a[0, 1] * s1 + a[0, 2] * s2 + bl[0] * node + bv[0]
        n1 = a[1, 0] * s0 + a[1, 1] * s1 + a[1, 2] * s2 + bl[1] * node + bv[1]
        n2 = a[2, 0] * s0 + a[2, 1] * s1 + a[2, 2] * s2 + bl[2] * node + bv[2]
        return (g_new, soc_new, n0, n1, n2), (y, soc_new)

    carry0 = (g0, soc0, x0[:, 0], x0[:, 1], x0[:, 2])
    xs = [rack_power, ramp01 if slew is not None else corr]
    if masked:
        xs.append(idxvec if events else w_all)
    (g_f, soc_f, s0, s1, s2), (grid, soc_t) = jax.lax.scan(
        step, carry0, tuple(xs)
    )
    x_f = jnp.stack([s0, s1, s2], axis=-1)
    if health is None:
        return grid, soc_t, (g_f, soc_f, x_f), None
    (c0, c1, eps, kappa), hs = health
    (prev_soc, last_ext, direction, half_cycles, cycle_damage, max_dod,
     charge_soc, discharge_soc, soc_sum, soc_sq_sum, samples) = hs
    prev_t = jnp.concatenate(
        [jnp.broadcast_to(prev_soc, soc_t[:1].shape), soc_t[:-1]], axis=0
    )
    delta = soc_t - prev_t
    step_dir = jnp.where(delta > eps, 1.0, jnp.where(delta < -eps, -1.0, 0.0))

    def hbody(carry, inp):
        last_ext, direction, half_cycles, damage, max_dod = carry
        prev, sd = inp
        rev = (sd * direction) < 0.0
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
        dmg = half_w * powd
        return (
            jnp.where(rev, prev, last_ext),
            jnp.where(sd != 0.0, sd, direction),
            half_cycles + revf,
            damage + revf * dmg,
            jnp.maximum(max_dod, revf * depth),
        ), None

    (last_ext, direction, half_cycles, damage, max_dod), _ = jax.lax.scan(
        hbody,
        (last_ext, direction, half_cycles, cycle_damage, max_dod),
        (prev_t, step_dir),
    )
    h_out = (
        soc_t[-1], last_ext, direction, half_cycles, damage, max_dod,
        charge_soc + jnp.sum(jnp.maximum(delta, 0.0), axis=0),
        discharge_soc + jnp.sum(jnp.maximum(-delta, 0.0), axis=0),
        soc_sum + jnp.sum(soc_t, axis=0),
        soc_sq_sum + jnp.sum(soc_t * soc_t, axis=0),
        samples + jnp.int32(t),
    )
    return grid, soc_t, (g_f, soc_f, x_f), h_out


# -------------------------------------------------------------- admm_iterate


def admm_iterate(
    kkt_stack: jax.Array,  # (2h, 5h) [sigma K^-1 | K^-1 A'] stacked
    g_blk: jax.Array,  # (h, 2h) SoC-constraint rows of A (A = [I; G])
    kq: jax.Array,  # (2h, ...) hoisted K^-1 q
    lo: jax.Array,  # (3h, ...)
    hi: jax.Array,
    x0: jax.Array,  # (2h, ...)
    z0: jax.Array,  # (3h, ...)
    y0: jax.Array,  # (3h, ...)
    *,
    rho: float,
    iters: int,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused batched-ADMM iteration loop (the controller QP inner loop).

    Exploits the plan's constraint structure ``A = [I_2h; G]``: the
    x-update's two K^-1 GEMMs collapse into one stacked
    ``(2h, 5h) @ (5h, R)`` product, and ``A x`` needs only the ``(h, 2h)``
    SoC block — the box rows of ``A x`` are ``x`` itself (exactly: the
    identity block contributes bitwise-equal rows).  Per iteration this is
    12h^2 R MACs versus 16h^2 R for the unfused pair, with x/z/y staying
    in one fused loop body (no per-iteration HBM round-trips on the Pallas
    path).  The stacked GEMM reassociates each output dot (one 5h-term sum
    instead of 2h- and 3h-term partials added), so x agrees with the
    unfused formulation to GEMM rounding, not bitwise — the controller
    equivalence tests bound this against the build-per-step oracle.
    """
    rho = jnp.float32(rho)
    # Full f32 products on every backend (a TPU's default is fewer MXU
    # passes); on the CPU this is the default anyway.
    dot = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)

    def body(carry, _):
        x, z, y = carry
        x_new = dot(kkt_stack, jnp.concatenate([x, rho * z - y], axis=0)) - kq
        ax = jnp.concatenate([x_new, dot(g_blk, x_new)], axis=0)
        z_new = jnp.clip(ax + y / rho, lo, hi)
        y_new = y + rho * (ax - z_new)
        return (x_new, z_new, y_new), None

    (x, z, y), _ = jax.lax.scan(body, (x0, z0, y0), None, length=iters)
    return x, z, y


# ------------------------------------------------------------------- rmsnorm


def rmsnorm(x: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    """RMSNorm over the last axis: x * w / rms(x)."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32)).astype(dtype)


# ----------------------------------------------------------------- gemm_burn


def gemm_burn(a: jax.Array, b: jax.Array, n_iters: int = 1) -> jax.Array:
    """Burn-kernel semantics: the mean of ``n_iters`` evaluations of A @ B.

    Numerically equal to A @ B; the iteration count is the duty-cycle knob
    that makes the kernel burn n_iters x the FLOPs (the compiler cannot
    elide the loop because each term is accumulated).
    """
    acc = jnp.zeros((a.shape[0], b.shape[1]), jnp.float32)

    def body(i, acc):
        return acc + jnp.dot(a, b, preferred_element_type=jnp.float32)

    acc = jax.lax.fori_loop(0, n_iters, body, acc)
    return (acc / n_iters).astype(a.dtype)


# ----------------------------------------------------- flash attention (fwd)


def attention(
    q: jax.Array,  # (B, H, Tq, D)
    k: jax.Array,  # (B, Hkv, Tk, D)
    v: jax.Array,  # (B, Hkv, Tk, D)
    *,
    causal: bool = True,
    scale: float | None = None,
    bias: jax.Array | None = None,  # broadcastable to (B, H, Tq, Tk)
    chunk_q: int = 1024,
) -> jax.Array:
    """Reference softmax attention with GQA (H a multiple of Hkv).

    For long sequences (Tq > chunk_q, no bias) queries are processed in
    scanned, rematerialized blocks so peak memory is O(chunk_q * Tk)
    rather than O(Tq * Tk) — this is the compile path for the 32k-token
    dry-run shapes on the CPU/fallback backend (the Pallas kernel covers
    TPU execution).
    """
    b, h, tq, d = q.shape
    hkv = k.shape[1]
    if scale is None:
        scale = 1.0 / (d**0.5)
    groups = h // hkv
    kx = jnp.repeat(k, groups, axis=1)
    vx = jnp.repeat(v, groups, axis=1)
    tk = kx.shape[2]

    def block(q_blk, q_offset):
        # q_blk: (B, H, Bq, D); absolute position = q_offset + row + (tk - tq)
        logits = jnp.einsum("bhqd,bhkd->bhqk", q_blk, kx).astype(jnp.float32) * scale
        if bias is not None:
            logits = logits + bias.astype(jnp.float32)
        if causal:
            rows = q_offset + jnp.arange(q_blk.shape[2]) + (tk - tq)
            mask = jnp.arange(tk)[None, :] <= rows[:, None]
            logits = jnp.where(mask[None, None], logits, -1e30)
        p = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), vx)

    if tq <= chunk_q or tq % chunk_q != 0 or bias is not None:
        return block(q, jnp.asarray(0))

    qb = q.reshape(b, h, tq // chunk_q, chunk_q, d).transpose(2, 0, 1, 3, 4)

    @jax.checkpoint
    def body(i, q_blk):
        return i + chunk_q, block(q_blk, i)

    _, out = jax.lax.scan(body, jnp.asarray(0), qb)
    # output feature dim follows V (MLA: q/k are 192-dim, v is 128-dim)
    return out.transpose(1, 2, 0, 3, 4).reshape(b, h, tq, vx.shape[-1])


# ----------------------------------------------------------------- rwkv6 scan


def rwkv6_chunked(
    r: jax.Array,  # (B, H, T, D)
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,  # decay in (0, 1)
    u: jax.Array,  # (H, D)
    state0: jax.Array | None = None,  # (B, H, D, D)
    *,
    chunk: int = 32,
) -> tuple[jax.Array, jax.Array]:
    """Chunk-parallel RWKV-6 (EXPERIMENTS §Perf-2).

    Mathematically identical to ``rwkv6_scan`` but restructured so the
    (D, D) state is read/written once per *chunk* instead of once per
    *step* (memory term / chunk) and the inner work becomes (L, D) x (D, L)
    matmuls (MXU-friendly) instead of per-step outer products:

      A[t,s]   = (r_t * W_{t-1}) . (k_s / W_s)          s < t   (intra)
      o_t      = tril(A,-1) @ v + (r_t*u*k_t).v_t + (r_t*W_{t-1}) @ S_in
      S_out    = diag(W_L) S_in + (k_s * W_L/W_s)^T v

    with W_t = prod_{s<=t} w_s (per channel, fp32 logs for stability;
    ``chunk`` bounds the exponent range).

    Numerics: the factored intermediates exp(±cum) can overflow fp32 when
    per-step decay is extreme (found by adversarial testing at w=0.01 over
    a 64-chunk).  Exponents are clamped to ±CLAMP: any pair whose TRUE
    relative decay is below e^-CLAMP contributes ~0 and stays ~0 after
    clamping, so accuracy holds whenever per-chunk total decay
    >= e^-CLAMP, i.e. mean per-step w >= exp(-CLAMP/chunk) (~0.29 at
    chunk=32) — far below any decay this architecture's parameterization
    reaches in practice; the sequential oracle remains available via
    ``ops.rwkv6_scan(algorithm="sequential")`` for pathological regimes.
    """
    b, h, t, d = r.shape
    if state0 is None:
        state0 = jnp.zeros((b, h, d, d), jnp.float32)
    if t % chunk != 0 or t <= chunk:
        return rwkv6_scan(r, k, v, w, u, state0)

    nc = t // chunk
    shp = (b, h, nc, chunk, d)
    rc = r.astype(jnp.float32).reshape(shp)
    kc = k.astype(jnp.float32).reshape(shp)
    vc = v.astype(jnp.float32).reshape(shp)
    lw = jnp.log(jnp.maximum(w.astype(jnp.float32), 1e-30)).reshape(shp)
    cum = jnp.cumsum(lw, axis=3)  # inclusive
    cum_prev = cum - lw  # exclusive: W_{t-1}
    total = cum[:, :, :, -1:, :]  # log W_L

    clamp = 40.0
    r_tilde = rc * jnp.exp(jnp.clip(cum_prev, -clamp, clamp))  # r_t * W_{t-1}
    k_tilde = kc * jnp.exp(jnp.clip(-cum, -clamp, clamp))  # k_s / W_s
    k_tail = kc * jnp.exp(jnp.clip(total - cum, -clamp, clamp))  # k_s W_L/W_s

    # intra-chunk attention-like matrix (strictly lower triangular)
    a_mat = jnp.einsum("bhctd,bhcsd->bhcts", r_tilde, k_tilde)
    mask = jnp.tril(jnp.ones((chunk, chunk), bool), k=-1)
    a_mat = jnp.where(mask[None, None, None], a_mat, 0.0)
    o_intra = jnp.einsum("bhcts,bhcsd->bhctd", a_mat, vc)
    # current-token bonus
    o_diag = jnp.einsum("bhctd,bhctd->bhct", rc * u[None, :, None, None, :], kc)[
        ..., None
    ] * vc
    # chunk state contributions
    s_add = jnp.einsum("bhcsd,bhcse->bhcde", k_tail, vc)  # (B,H,nc,D,D)
    w_chunk = jnp.exp(total[:, :, :, 0, :])  # (B,H,nc,D)

    def scan_chunks(s, inp):
        s_a, w_c, r_t = inp  # (B,H,D,D), (B,H,D), (B,H,L,D)
        o_inter = jnp.einsum("bhtd,bhde->bhte", r_t, s)
        s_next = w_c[..., :, None] * s + s_a
        return s_next, o_inter

    s_f, o_inter = jax.lax.scan(
        scan_chunks,
        state0.astype(jnp.float32),
        (jnp.moveaxis(s_add, 2, 0), jnp.moveaxis(w_chunk, 2, 0),
         jnp.moveaxis(r_tilde, 2, 0)),
    )
    o_inter = jnp.moveaxis(o_inter, 0, 2)  # (B,H,nc,L,D)
    out = (o_intra + o_diag + o_inter).reshape(b, h, t, d)
    return out.astype(r.dtype), s_f


def rwkv6_scan(
    r: jax.Array,  # (B, H, T, D) receptance
    k: jax.Array,  # (B, H, T, D) key
    v: jax.Array,  # (B, H, T, D) value
    w: jax.Array,  # (B, H, T, D) per-channel decay in (0, 1): exp(-exp(...))
    u: jax.Array,  # (H, D) bonus for the current token
    state0: jax.Array | None = None,  # (B, H, D, D)
    *,
    chunk: int = 128,
) -> tuple[jax.Array, jax.Array]:
    """RWKV-6 (Finch) time-mix recurrence.

    S_t = diag(w_t) S_{t-1} + k_t^T v_t        (outer product, (D, D))
    o_t = r_t (S_{t-1} + diag(u) k_t^T v_t)

    Shapes follow the head-major layout; returns (out (B,H,T,D), S_T).
    """
    b, h, t, d = r.shape
    if state0 is None:
        state0 = jnp.zeros((b, h, d, d), jnp.float32)

    def step(s, inp):
        r_t, k_t, v_t, w_t = inp  # (B, H, D) each
        kv = k_t[..., :, None] * v_t[..., None, :]  # (B, H, D, D)
        out = jnp.einsum("bhd,bhde->bhe", r_t, s + u[None, :, :, None] * kv)
        s_next = w_t[..., :, None] * s + kv
        return s_next, out

    xs = tuple(jnp.moveaxis(a, 2, 0).astype(jnp.float32) for a in (r, k, v, w))

    # Chunked remat: without it the backward pass stores the (D, D) state
    # for every timestep (hundreds of GB at 4k+ tokens); chunking stores one
    # state per ``chunk`` steps and recomputes inside.
    if t % chunk == 0 and t > chunk:
        n_chunks = t // chunk
        xs_c = tuple(a.reshape((n_chunks, chunk) + a.shape[1:]) for a in xs)

        @jax.checkpoint
        def chunk_body(s, inp):
            return jax.lax.scan(step, s, inp)

        s_f, out = jax.lax.scan(chunk_body, state0, xs_c)
        out = out.reshape((t,) + out.shape[2:])
    else:
        s_f, out = jax.lax.scan(step, state0, xs)
    return jnp.moveaxis(out, 0, 2).astype(r.dtype), s_f
