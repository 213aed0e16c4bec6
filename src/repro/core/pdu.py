"""The composed EasyRider PDU (paper §4-§6): filter + ESS + controller.

Signal chain (per-unit, powers as fractions of rated rack power):

    rack power --(ESS ramp control, Eq. 2)--> node power g
               --(passive LC + damping)-----> grid power

The ESS stage removes low-frequency content (>= f_b = beta/2pi); the LC
stage removes high-frequency content (>= f_f).  The total response is the
product of the two transfer functions (paper Fig. 7).  The software
controller runs every ``cfg.dt`` (5 s) seconds of simulated time and issues
milliamp-scale corrective currents that nudge the battery SoC toward the
outer-loop target without perturbing the grid-facing waveform.

Everything is per-unit: physical component values from ``sizing`` are
converted with the rack base impedance so one code path serves the 10 kW
prototype and 1 MW racks identically.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import compliance, controller as ctrl, ess, filters, health as hlt, \
    profiling as _prof, safemode as smode, sizing
from repro.kernels import ops
from repro.power import faults as flt
from repro.utils import pytree_dataclass, static_field


@pytree_dataclass
class PDUConfig:
    filter_params: filters.LCFilterParams  # per-unit
    ess_params: ess.ESSParams
    controller: ctrl.ControllerConfig
    health: hlt.HealthParams = None  # aging model (used when track_health)
    safemode_params: smode.SafeModeConfig = None  # watchdog knobs (when safemode)
    sample_dt: float = static_field(default=1e-3)  # trace sample period [s]
    software_enabled: bool = static_field(default=True)
    # Fold per-sample battery wear telemetry (core.health) into the
    # conditioning scan.  Pure observation — grid/SoC outputs are
    # unchanged — but it costs a second per-sample scan, so it is opt-in.
    track_health: bool = static_field(default=False)
    # Degraded-mode conditioning: honor per-interval ESS availability masks
    # (offline units run in LC passthrough), bridge NaN sensor dropouts
    # with a last-good-sample hold, and trip measurement-blind racks into
    # passthrough.  Static so the fault-free path stays structurally (and
    # bitwise) identical to builds without this feature.
    degraded_mode: bool = static_field(default=False)
    # Supervisory safe mode (core.safemode): per-rack NORMAL → PASSTHROUGH →
    # QUARANTINE state machine driven in-jit by the ADMM divergence watchdog
    # and the state-corruption sanitizer.  Static for the same reason as
    # degraded_mode: with safemode=False the compiled program is
    # structurally (and bitwise) identical to the unsupervised build.
    safemode: bool = static_field(default=False)


def per_unit_filter(s: sizing.SizingResult, rack: sizing.RackRating) -> filters.LCFilterParams:
    """Convert physical component values to the per-unit system."""
    z = rack.v_dc**2 / rack.p_rated_w
    return filters.LCFilterParams.create(
        l_f=s.l_f / z, c_f=s.c_f * z, r_da=s.r_da / z, l_da=s.l_da * (1.0 / z)
    )


def make_pdu(
    rack: sizing.RackRating | None = None,
    grid: compliance.GridSpec | None = None,
    *,
    sample_dt: float = 1e-3,
    f_f_hz: float = 4.0,
    soc_window: tuple[float, float] = (0.1, 0.9),
    capacity_margin: float = 4.0,
    ramp_margin: float = 1.6,
    software_enabled: bool = True,
    controller_cfg: ctrl.ControllerConfig | None = None,
    health_params: hlt.HealthParams | None = None,
    track_health: bool = False,
    degraded_mode: bool = False,
    safemode: bool = False,
    safemode_params: smode.SafeModeConfig | None = None,
) -> PDUConfig:
    """Size and assemble an EasyRider PDU for a rack + grid spec.

    Default parameters reproduce the paper's prototype design point:
    beta = 0.1/s, alpha = 1e-4, f_c = 2 Hz, f_f ~= 4 Hz.

    Capacity: Appendix A.1 Eq. 8 with gamma = usable SoC window gives the
    floor for a *single* worst-case transient starting at the favorable
    window edge.  Operating mid-band for symmetric headroom (paper §6)
    doubles the need, and ongoing iteration cycling adds more; like the
    paper's intentionally oversized 74 Ah pack we apply ``capacity_margin``
    (default 4x) on top of the Eq. 8 floor.  Tests verify both the Eq. 8
    bound itself and that the margined design rides the testbench without
    SoC saturation.

    Ramp margin: the damped LC stage transiently amplifies the *slope* of
    ramp-limited kinks by up to ~1.5x near its resonance, so the ESS is
    designed to beta/ramp_margin; the composed grid-facing ramp then meets
    the spec beta with margin (verified end-to-end in tests).
    """
    rack = rack or sizing.prototype_rack()
    grid = grid or compliance.GridSpec.create()
    beta = float(grid.beta) / ramp_margin
    gamma = soc_window[1] - soc_window[0]
    s = sizing.size_system(rack, beta=beta, f_f_hz=f_f_hz, gamma=gamma)
    q_max_seconds = capacity_margin * s.battery_energy_j / rack.p_rated_w
    ess_params = ess.ESSParams.create(
        beta=beta,
        q_max_seconds=q_max_seconds,
        p_max=max(rack.epsilon * 1.25, 1.0),
        soc_safe_min=soc_window[0],
        soc_safe_max=soc_window[1],
    )
    return PDUConfig(
        filter_params=per_unit_filter(s, rack),
        ess_params=ess_params,
        controller=controller_cfg or ctrl.ControllerConfig.create(),
        health=health_params or hlt.HealthParams.create(),
        safemode_params=(
            (safemode_params or smode.SafeModeConfig.create()) if safemode else None
        ),
        sample_dt=sample_dt,
        software_enabled=software_enabled,
        track_health=track_health,
        degraded_mode=degraded_mode,
        safemode=safemode,
    )


class PDUState(NamedTuple):
    filter_state: jax.Array  # (..., 3)
    filter_obj: filters.DiscreteFilter
    ess_state: ess.ESSState
    u_prev: jax.Array  # last normalized controller command
    cmd_applied: jax.Array  # corrective power applied at the last sample
    cmd_target: jax.Array  # corrective power to slew toward this interval
    soc_ema: jax.Array  # BMS measurement filter (slow SoC estimate)
    qp_warm: ctrl.QPWarmState  # ADMM iterates carried across intervals/chunks
    health: hlt.HealthState  # battery wear telemetry (zeros unless tracked)
    # Degraded-mode state (always present so the carry structure is uniform):
    # operator/manual ESS availability override (1 = available) and the last
    # finite sample seen per rack (seeds the sensor-dropout bridge).
    ess_online: jax.Array = None
    last_good: jax.Array = None
    # Supervisory safe-mode state machine (always present so the carry
    # structure is uniform; all-NORMAL zeros unless cfg.safemode).
    safemode: smode.SafeModeState = None


def init_state(cfg: PDUConfig, rack_power0: jax.Array, soc0: float = 0.5) -> PDUState:
    """Steady-state initialization at a constant starting power.

    NaN entries in ``rack_power0`` (a rack whose sensor is dark at the very
    first sample) seed from the fleet's finite mean instead — a no-op for
    clean traces, and it keeps every engine's seeding identical under
    sensor-dropout fault schedules.
    """
    filt = filters.make_discrete_filter(cfg.filter_params, cfg.sample_dt)
    r0 = jnp.asarray(rack_power0, jnp.float32)
    finite = jnp.isfinite(r0)
    r0 = jnp.where(finite, r0, jnp.nan_to_num(jnp.nanmean(r0), nan=0.5))
    u0 = jnp.stack([jnp.ones_like(r0), r0], axis=-1)  # [v_in=1, i_load=r0]
    x0 = jnp.vectorize(lambda u: filters.steady_state(filt, u), signature="(m)->(n)")(u0)
    return PDUState(
        filter_state=x0,
        filter_obj=filt,
        ess_state=ess.ESSState(g_filter=r0, soc=jnp.full_like(r0, soc0)),
        u_prev=jnp.zeros_like(r0),
        cmd_applied=jnp.zeros_like(r0),
        cmd_target=jnp.zeros_like(r0),
        soc_ema=jnp.full_like(r0, soc0),
        qp_warm=ctrl.init_warm(cfg.controller.horizon, r0.shape),
        health=hlt.init_state(jnp.full_like(r0, soc0)),
        ess_online=jnp.ones_like(r0),
        # Distinct buffer from ess_state.g_filter: donated engines reject
        # the same array appearing twice in one argument list.
        last_good=jnp.copy(r0),
        safemode=smode.init_state(r0.shape),
    )


class Telemetry(NamedTuple):
    soc: jax.Array  # (n_ctrl, ...) SoC at each control interval
    command: jax.Array  # corrective power commanded per interval
    target: jax.Array  # outer-loop SoC target per interval
    qp_residual: jax.Array  # QP primal residual per interval (0 if sw off)
    # Campus means, computed INSIDE the interval scan from its materialized
    # operands.  A top-level ``jnp.mean(rack_power)`` next to the scan gives
    # XLA a second consumer of the rendered chunk, and its fusion pass
    # duplicates the whole producer chain (measured: the noise transform
    # ran twice per chunk in the scanned engine's fused jit) — reducing
    # over the scan's xs/output buffers instead keeps the producer
    # single-consumer while yielding bitwise-identical values (the rack
    # reduction of row t does not depend on which rows share the array).
    rack_mean: jax.Array = None  # (T,) mean of the (bridged) input trace
    grid_mean: jax.Array = None  # (T,) mean of the conditioned grid trace
    # Degraded-mode extra (None unless cfg.degraded_mode):
    ess_online: jax.Array = None  # (n_ctrl, ...) effective availability mask
    # Safe-mode extra (None unless cfg.safemode): the post-watchdog
    # supervisor mode per interval (0 NORMAL / 1 PASSTHROUGH / 2 QUARANTINE).
    safemode_mode: jax.Array = None


def bridge_sensors(
    last_good: jax.Array, rack_power: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Replace NaN (sensor-dropout) samples with the most recent finite
    sample per rack — ``last_good`` seeds racks whose first samples are
    dark.  Returns ``(bridged, new_last_good)``.

    The fill is a pure gather of the last finite sample at-or-before each
    index, so chunked bridging with the carried ``last_good`` reproduces
    whole-trace bridging bit-for-bit.
    """
    t = rack_power.shape[0]
    finite = jnp.isfinite(rack_power)
    idx = jnp.arange(t, dtype=jnp.int32).reshape((t,) + (1,) * (rack_power.ndim - 1))
    last_pos = jax.lax.associative_scan(
        jnp.maximum, jnp.where(finite, idx, -1), axis=0
    )
    vals = jnp.where(finite, rack_power, 0.0)
    held = jnp.take_along_axis(
        vals, jnp.broadcast_to(jnp.maximum(last_pos, 0), rack_power.shape), axis=0
    )
    bridged = jnp.where(last_pos >= 0, held, last_good)
    return bridged, bridged[-1]


def condition(
    cfg: PDUConfig,
    state: PDUState,
    rack_power: jax.Array,  # (T, ...) per-unit rack power
    *,
    idle_remaining_s: jax.Array | float = 0.0,
    qp_iters: int = 120,
    use_plan: bool = True,
    ess_online: jax.Array | None = None,
    ess_weight: jax.Array | None = None,
    faults: flt.FaultSchedule | None = None,
    chunk_start: jax.Array | int = 0,
    fault_edge: int = 1,
) -> tuple[jax.Array, PDUState, Telemetry]:
    """Condition a trace chunk; carries state across calls (streaming).

    The outer scan advances one controller interval (cfg.controller.dt
    seconds = k samples) at a time: the hardware path is simulated for k
    samples while the corrective command slews linearly from the previously
    applied value toward the latest controller output (battery converters
    ramp; command updates must not inject steps into the grid waveform),
    then one QP solve — fed the EMA-filtered BMS state-of-charge, so the
    software tracks slow drift rather than chasing per-iteration workload
    cycling — produces the next slew target.  If T is not a multiple of k
    the trace is zero-order-hold padded and the pad discarded.

    ``use_plan=True`` (default) factors the controller QP once outside the
    scan (``ctrl.make_plan``), solves all racks as one batched ADMM, and
    warm-starts each interval from ``state.qp_warm`` — the warm state rides
    in ``PDUState`` so chunked (streaming) calls stay bit-identical to one
    whole-trace call.  ``use_plan=False`` keeps the original per-interval
    build + factor + vmapped-solve path (the oracle for equivalence tests
    and the cold-start baseline for benchmarks).

    Degraded mode (``cfg.degraded_mode``): ``ess_online`` is a per-interval
    availability mask — ``(n_ctrl, ...)`` rows, or a single ``(...)`` mask
    applied to every interval — marking racks whose ESS unit has tripped
    offline; those racks condition in LC passthrough with zeroed controller
    commands and reset QP warm state.  NaN samples (sensor dropout) are
    bridged with a last-good-sample hold (``bridge_sensors``, seeded from
    ``state.last_good``), and a rack whose sensor is dark for an *entire*
    control interval trips a finite-guard: it is forced into passthrough
    for that interval regardless of the mask, so a blind controller never
    commands a live battery.  The effective mask actually applied and the
    per-sample mean of the bridged trace ride out in ``Telemetry``.

    Safe mode (``cfg.safemode``): the supervisory state machine of
    ``core.safemode`` rides the same scan.  Each interval starts with the
    state-corruption sanitizer (non-finite carry leaves quarantine the
    rack and reinitialize its slice to steady state), racks not in NORMAL
    mode are excluded from the hardware plane through the same
    ``ess_on`` weight degraded mode uses (LC passthrough), and after the
    QP solve the divergence watchdog folds the raw per-rack primal
    residual: racks over ``safemode_params.resid_threshold`` for
    ``trip_intervals`` consecutive intervals trip to PASSTHROUGH — their
    command is zeroed and their warm iterates reset — then probe their
    (cold-started) solve every interval until ``readmit_intervals``
    consecutive clean probes re-admit them.  ``Telemetry.safemode_mode``
    carries the post-watchdog per-interval mode rows.

    ``ess_weight`` (optional, shaped like ``rack_power``) is the hardware
    plane's *per-sample* availability weight: trips land at their true
    sample and the converter winds down/soft-starts over the schedule's
    edge window (``faults.ess_weight``) instead of snapping at the
    controller-interval boundary — without it, every trip in an interval
    hands its battery power to the grid on the same sample, a fabricated
    campus-synchronized step.  When given, the hardware path follows
    ``ess_weight`` (composed with the manual-override state and the
    finite-guard) while ``ess_online`` keeps governing the software plane
    (QP admission, command zeroing, telemetry).

    ``faults`` (mutually exclusive with explicit ``ess_online`` /
    ``ess_weight`` arrays) is the compiled fast path for the same
    semantics: pass the ``FaultSchedule`` itself plus the chunk's absolute
    ``chunk_start`` sample and the scenario's ``fault_edge`` width, and
    every degraded-mode signal is rendered from O(episodes) boundary
    events instead of streamed ``(T, R)`` blocks — the interval
    online/sensed masks are tiny ``(n_ctrl, R)`` schedule lookups, the
    NaN sensor bridge becomes a per-interval hold-index gather *inside*
    the scan body (on the materialized xs slice, so the rendered trace
    keeps a single consumer chain — EXPERIMENTS §Perf-8 records the
    producer-duplication pathology this avoids), and the per-sample ESS
    weight is rendered inside the megakernel from the episode tables
    (``ops.pdu_health_sim`` ``ess_events``).  Outputs are bit-identical
    to the streamed-array path at any chunk split and resume point.
    Safe-mode cfgs fall back to the streamed derivation (the supervisor
    composes its own per-sample hardware-weight ramps).
    """
    degraded = cfg.degraded_mode
    safemode = cfg.safemode
    if (ess_online is not None or ess_weight is not None) and not degraded:
        raise ValueError(
            "ess_online/ess_weight require a cfg with degraded_mode=True"
        )
    if faults is not None:
        if not degraded:
            raise ValueError("faults requires a cfg with degraded_mode=True")
        if ess_online is not None or ess_weight is not None:
            raise ValueError(
                "pass either a FaultSchedule or explicit ess_online/"
                "ess_weight arrays, not both"
            )
        if rack_power.ndim < 2:
            raise ValueError("the fault fast path needs a batched (T, R) trace")
    dt = cfg.sample_dt
    k = max(int(round(float(cfg.controller.dt) / dt)), 1)
    t = rack_power.shape[0]
    n_ctrl = -(-t // k)
    pad = n_ctrl * k - t
    batch = rack_power.shape[1:]

    fast = faults is not None and not safemode
    if faults is not None and safemode:
        # The supervisor slews its own per-sample hardware weight across
        # each interval; composing that ramp with in-kernel event
        # rendering would need a second weight operand, so safe-mode runs
        # keep the streamed derivation (identical values by the faults
        # equivalence contract).
        ess_online = flt.interval_online(faults, chunk_start, n_ctrl, k)
        ess_weight = flt.ess_weight(faults, chunk_start, t, fault_edge)
        faults = None

    if fast:
        cs = jnp.asarray(chunk_start, jnp.int32)
        t_last = cs + (t - 1)
        # Software plane + finite-guard, straight from the episode tables:
        # no isfinite/bridge pass over the rendered trace before the scan,
        # so the render's only consumer is the scan's xs buffer.
        sensed = flt.interval_sensed(faults, cs, n_ctrl, k, stop=cs + t)
        arg_rows = flt.interval_online(faults, cs, n_ctrl, k)
        hw_base = jnp.broadcast_to(
            state.ess_online, (n_ctrl,) + batch
        ) * sensed.astype(jnp.float32)
        on_rows = arg_rows * hw_base
        # Compact megakernel operand: (E, R) boundary tables + per-interval
        # absolute start samples (the per-sample weight renders in-kernel).
        ev_st = faults.ess_start.T
        ev_en = faults.ess_end.T
        i0_rows = cs + k * jnp.arange(n_ctrl, dtype=jnp.int32)
    elif degraded:
        finite = jnp.isfinite(rack_power)
        fpad = (
            jnp.concatenate([finite, jnp.repeat(finite[-1:], pad, axis=0)], axis=0)
            if pad
            else finite
        )
        # Finite-guard tripwire: an interval with zero finite samples means
        # the rack was measurement-blind for the whole control period.
        sensed = jnp.any(fpad.reshape((n_ctrl, k) + batch), axis=1)
        rack_power, last_good2 = bridge_sensors(state.last_good, rack_power)
        if ess_online is None:
            arg_rows = jnp.ones((n_ctrl,) + batch, jnp.float32)
        else:
            ess_online = jnp.asarray(ess_online, jnp.float32)
            if ess_online.ndim == rack_power.ndim - 1:  # one mask, all intervals
                ess_online = jnp.broadcast_to(ess_online, (n_ctrl,) + batch)
            arg_rows = ess_online
        # Manual-override state x finite-guard: applies to both planes.
        hw_base = jnp.broadcast_to(
            state.ess_online, (n_ctrl,) + batch
        ) * sensed.astype(jnp.float32)
        on_rows = arg_rows * hw_base
        if ess_weight is None:
            # Hardware follows the interval mask (legacy/manual path).
            hw_chunks = on_rows[:, None]
        else:
            ess_weight = jnp.asarray(ess_weight, jnp.float32)
            wpad = (
                jnp.concatenate(
                    [ess_weight, jnp.repeat(ess_weight[-1:], pad, axis=0)],
                    axis=0,
                )
                if pad
                else ess_weight
            )
            hw_chunks = (
                wpad.reshape((n_ctrl, k) + batch) * hw_base[:, None]
            )
    else:
        last_good2 = state.last_good

    padded = (
        jnp.concatenate([rack_power, jnp.repeat(rack_power[-1:], pad, axis=0)], axis=0)
        if pad
        else rack_power
    )
    chunks = padded.reshape((n_ctrl, k) + rack_power.shape[1:])

    filt = state.filter_obj
    meas_w = min(float(cfg.controller.dt) / float(cfg.controller.meas_tau), 1.0)

    if safemode:
        sm_cfg = (
            cfg.safemode_params
            if cfg.safemode_params is not None
            else smode.SafeModeConfig.create()
        )
        s_mid = jnp.asarray(cfg.controller.s_mid, jnp.float32)
        # Steady-state map for quarantine reinit: x_ss(r) = (I-Ad)^-1 Bd
        # [1, r] — hoisted out of the scan, shared by every rack.
        eye = jnp.eye(filt.ad.shape[0], dtype=filt.ad.dtype)
        ss_mat = jnp.linalg.solve(eye - filt.ad, filt.bd)  # (3, 2)

    ep = cfg.ess_params
    # Factor-once plan: P, A and the KKT Cholesky depend only on config, so
    # they are hoisted out of the interval scan (and shared by every rack).
    with _prof.scope("controller"):
        plan = ctrl.make_plan(cfg.controller, cfg.ess_params) if (
            cfg.software_enabled and use_plan
        ) else None
    hw_kw = dict(
        beta=float(ep.beta), dt=dt, q_max=float(ep.q_max),
        eta_c=float(ep.eta_c), eta_d=float(ep.eta_d),
        p_max=float(ep.p_max), soc_min=float(ep.soc_safe_min),
        soc_max=float(ep.soc_safe_max),
    )
    hconsts = hlt.step_consts(cfg.health) if cfg.track_health else None

    def interval(carry, xs):
        if safemode:
            carry, sm = carry
        else:
            sm = None
        if fast:
            (
                x_f, es, u_prev, cmd_applied, cmd_target, soc_ema, warm,
                hstate, step_idx, lg,
            ) = carry
        else:
            (
                x_f, es, u_prev, cmd_applied, cmd_target, soc_ema, warm,
                hstate, step_idx,
            ) = carry
        if fast:
            rack_chunk, on_row, base_row, i0 = xs
            # --- in-body sensor bridge (schedule-compiled) ---------------
            # Operates on the materialized (k, R) xs slice: dark samples
            # take the raw value at the covering episode's ``start - 1``
            # (always finite — episodes are coalesced with >= 1 healthy
            # sample between them), or the carried last-good row when that
            # index precedes this interval.  Bit-identical to running
            # ``bridge_sensors`` over the whole chunk (the associative-scan
            # bridge gathers the same raw samples), without giving the
            # pre-scan render a second consumer.  Indices clamp to the
            # last real sample so ZOH pad rows replicate its bridge.
            idx = jnp.minimum(i0 + jnp.arange(k, dtype=jnp.int32), t_last)
            dark, hold = flt.sensor_dark_hold(faults, idx)
            loc = hold - i0
            held = jnp.take_along_axis(
                jnp.where(dark, 0.0, rack_chunk), jnp.clip(loc, 0, k - 1), axis=0
            )
            rack_chunk = jnp.where(
                dark, jnp.where(loc >= 0, held, lg), rack_chunk
            )
            lg = rack_chunk[-1]
        elif degraded:
            rack_chunk, on_row, hw_chunk = xs
        else:
            rack_chunk = xs

        # --- safe mode: state-corruption sanitizer -----------------------
        # Runs at the START of the interval, so non-finite state — whether
        # injected between windows or produced by the previous interval —
        # is quarantined and reinitialized before it can reach the
        # hardware path or the solver.
        if safemode:
            r0 = rack_chunk[0]
            r0 = jnp.where(jnp.isfinite(r0), r0, 0.5)
            nonfin = lambda a: ~jnp.isfinite(a)
            corrupt = (
                nonfin(es.soc) | nonfin(es.g_filter)
                | jnp.any(nonfin(x_f), axis=-1)
                | nonfin(u_prev) | nonfin(cmd_applied) | nonfin(cmd_target)
                | nonfin(soc_ema)
                | jnp.any(nonfin(warm.x), axis=0)
                | jnp.any(nonfin(warm.z), axis=0)
                | jnp.any(nonfin(warm.y), axis=0)
            )
            for leaf in hstate:
                if jnp.issubdtype(leaf.dtype, jnp.floating):
                    corrupt = corrupt | nonfin(leaf)
            fin = lambda a, v: jnp.where(jnp.isfinite(a), a, v)
            x_ss = ss_mat[:, 0] + r0[..., None] * ss_mat[:, 1]
            # Reinit is per-LEAF where the leaf itself went non-finite:
            # hardware-continuous leaves (LC filter state, grid filter,
            # applied command) keep their finite values so containment
            # never steps the grid waveform, while the corrupted leaves
            # land on the clean steady state.  Supervisor-internal leaves
            # (warm iterates, wear accumulators, controller reference) do
            # reset for the whole corrupted rack — a deterministic
            # cold-started probe needs them clean, and they never touch
            # the waveform directly.
            es = ess.ESSState(
                g_filter=fin(es.g_filter, r0), soc=fin(es.soc, s_mid)
            )
            x_f = fin(x_f, x_ss)
            u_prev = jnp.where(corrupt, 0.0, u_prev)
            cmd_applied = fin(cmd_applied, 0.0)
            cmd_target = jnp.where(corrupt, 0.0, cmd_target)
            soc_ema = fin(soc_ema, s_mid)
            warm = ctrl.reset_warm_where(warm, corrupt)
            hstate = hlt.reinit_where(hstate, corrupt, s_mid)
            sm = smode.quarantine(sm, corrupt)
            # Hardware admission reads the PRE-watchdog mode: a rack that
            # only trips at this interval's solve still conditioned this
            # interval (the trip gates its NEXT command), exactly like the
            # degraded-mode interval-boundary semantics.  Containment is
            # two-tier, matching what actually failed:
            #
            # * PASSTHROUGH (diverged QP) contains the SOFTWARE plane only
            #   — command zeroed, warm reset, probing — while the
            #   autonomous hardware ramp filter keeps smoothing (it needs
            #   no solver).  Parking a healthy battery would expose raw
            #   training bursts: ~5% of racks unconditioned already breaks
            #   the campus ramp limit, i.e. the containment would inject
            #   the very transient the conditioner exists to prevent.
            # * QUARANTINE (corrupted state) falls all the way to LC
            #   passthrough: the rack's SoC/filter tracking cannot be
            #   trusted until the reinitialized state survives the
            #   hysteresis window.  The fall is GRACEFUL — the hardware
            #   plane stays live while the last applied command slews to
            #   zero (one interval), then the converter winds down.
            sm_gate = jnp.where(
                (sm.mode == smode.QUARANTINE)
                & (cmd_applied == 0.0) & (cmd_target == 0.0),
                0.0,
                1.0,
            )
            # Converter wind-down / soft-start: the applied ESS weight
            # slews linearly across the interval from its carried value
            # to the gate target.  At weight 0 the node sees RAW rack
            # power (LC passthrough drops the smoothed setpoint g), so a
            # hard 0/1 flip would step the campus waveform in one sample
            # — exactly the transient the conditioner exists to prevent.
            # Clean racks compute 1 + (1-1)*ramp == 1.0 exactly, keeping
            # the supervised clean path bitwise identical.
            ramp_w = (jnp.arange(1, k + 1, dtype=jnp.float32) / k).reshape(
                (k,) + (1,) * sm_gate.ndim
            )
            sm_w = sm.hw_weight + (sm_gate - sm.hw_weight) * ramp_w
            sm = sm._replace(hw_weight=sm_gate)

        # --- hardware path: interval-resident megakernel -----------------
        # One call simulates the whole interval: fused ESS + SoC + LC
        # (1.6x over the staged pipeline, EXPERIMENTS §Perf-1), with the
        # corrective-command slew rendered per step from the (applied,
        # target) rows — the (k, R) ramp profile is never materialized —
        # and, when track_health, the battery-wear fold computed in the
        # same launch (Pallas kernel on TPU keeps all of it in VMEM;
        # the jnp reference preserves the bitwise fold contract, see
        # ref.pdu_health_sim / EXPERIMENTS §Perf-7).
        batched = rack_chunk.ndim > 1
        lift = (lambda x: x) if batched else (lambda x: x[None])
        rc = rack_chunk if batched else rack_chunk[:, None]
        g0, s0, xf0 = lift(es.g_filter), lift(es.soc), lift(x_f)
        if fast:
            # Per-sample ESS weight rendered in-kernel from the episode
            # tables (same boundary selection + clip arithmetic as
            # faults.ess_weight, so bitwise vs the streamed product).
            mask_kw = dict(
                ess_events=(ev_st, ev_en, base_row, i0, t_last),
                ess_edge=fault_edge,
            )
        elif degraded:
            hw = jnp.broadcast_to(hw_chunk, (k,) + batch)
            if safemode:
                hw = hw * sm_w
            mask_kw = dict(ess_on=hw if batched else hw[:, None])
        elif safemode:
            # Same two-plane machinery as degraded mode: non-NORMAL racks
            # wind down to LC passthrough.  An all-ones weight is bitwise-
            # identical to the unmasked kernel path (PR-6 contract), so a
            # clean run with supervision on matches supervision off bit
            # for bit.
            hw = jnp.broadcast_to(sm_w, (k,) + batch)
            mask_kw = dict(ess_on=hw if batched else hw[:, None])
        else:
            mask_kw = {}
        if cfg.track_health:
            health_in = (hconsts, tuple(lift(leaf) for leaf in hstate))
        else:
            health_in = None
        grid, _soc_path, (g_f, soc_f, x_new), h_leaves = ops.pdu_health_sim(
            rc, g0, s0, xf0, filt.ad, filt.bd, filt.c[0],
            slew=(lift(cmd_applied), lift(cmd_target)),
            health=health_in, guard=safemode, **mask_kw, **hw_kw,
        )
        # Campus means over the scan-resident buffers (see Telemetry).
        rack_mean_row = jnp.mean(rc, axis=1)
        grid_mean_row = jnp.mean(grid, axis=1)
        if not batched:
            grid, g_f, soc_f, x_new = grid[:, 0], g_f[0], soc_f[0], x_new[0]
            if cfg.track_health:
                h_leaves = tuple(leaf[0] for leaf in h_leaves)
        es2 = ess.ESSState(g_filter=g_f, soc=soc_f)
        x_f2 = x_new

        # --- health telemetry (folded inside the megakernel) --------------
        if cfg.track_health:
            hstate2 = hlt.HealthState(*h_leaves)
            # Wear feedback reads the PRE-interval state: one control
            # interval (5 s) of staleness is nothing on aging timescales,
            # and it takes the wear fold off the controller's critical
            # path.
            wear = hlt.cycle_life_fraction(cfg.health, hstate)
        else:
            hstate2 = hstate
            wear = jnp.asarray(0.0, jnp.float32)

        # --- software path: one controller step --------------------------
        with _prof.scope("controller"):
            idle_left = jnp.maximum(
                jnp.asarray(idle_remaining_s, jnp.float32) - step_idx * k * dt, 0.0
            )
            s_target = ctrl.select_target(
                cfg.controller, cfg.ess_params, idle_left, wear
            )
            soc_meas = soc_ema + meas_w * (es2.soc - soc_ema)

            def run_ctrl(soc, up, tgt):
                out = ctrl.inner_loop_step(
                    cfg.controller, cfg.ess_params, soc, tgt, up, qp_iters=qp_iters
                )
                return out.corrective_power, out.qp_primal_residual

            if cfg.software_enabled and plan is not None:
                out, warm2 = ctrl.inner_loop_step_plan(
                    cfg.controller, cfg.ess_params, plan, soc_meas, s_target,
                    u_prev, warm, qp_iters=qp_iters,
                    active=on_row if degraded else None,
                )
                new_cmd = out.corrective_power
                resid = out.qp_primal_residual
            elif cfg.software_enabled:
                vec_ctrl = run_ctrl
                for _ in range(soc_meas.ndim):
                    vec_ctrl = jax.vmap(vec_ctrl)
                new_cmd, resid = vec_ctrl(
                    soc_meas, jnp.broadcast_to(u_prev, soc_meas.shape),
                    jnp.broadcast_to(s_target, soc_meas.shape),
                )
                if degraded:
                    new_cmd = jnp.where(on_row > 0, new_cmd, 0.0)
                    resid = jnp.where(on_row > 0, resid, 0.0)
                warm2 = warm
            else:
                new_cmd = jnp.zeros_like(soc_meas)
                resid = jnp.zeros_like(soc_meas)
                warm2 = warm

        # --- safe mode: ADMM divergence watchdog -------------------------
        soc_row = es2.soc
        if safemode:
            # The watchdog folds the RAW residual (tripped racks keep
            # probing; degraded-offline racks arrive pre-masked to zero so
            # availability faults never read as solver faults), then the
            # post-update mode gates the software plane: no non-NORMAL
            # rack ever commands a live battery, and its warm iterates are
            # reset so the next probe is a deterministic cold start.
            # Per-interval command veto: an over-threshold (or non-finite)
            # solve never gets its command applied, even before the trip
            # streak completes — the rack HOLDS its last accepted command
            # (still approximately right for one interval) instead of
            # slewing toward a diverged iterate.  On clean runs the
            # predicate is never true, so the supervised clean path stays
            # bitwise identical.
            bad_now = (resid > sm_cfg.resid_threshold) | ~jnp.isfinite(resid)
            new_cmd = jnp.where(bad_now, cmd_target, new_cmd)
            sm = smode.residual_update(sm_cfg, sm, resid)
            sm_ok = sm.mode == smode.NORMAL
            new_cmd = jnp.where(sm_ok, new_cmd, 0.0)
            resid = jnp.where(sm_ok, resid, 0.0)
            warm2 = ctrl.reset_warm_where(warm2, ~sm_ok)
            # Telemetry guard: a SoC driven non-finite by this interval's
            # sim stays in the carry (the sanitizer quarantines it next
            # interval) but never reaches campus aggregates.
            soc_row = jnp.where(jnp.isfinite(soc_row), soc_row, s_mid)
        new_u_prev = new_cmd / cfg.controller.i_max

        telem = (
            soc_row, new_cmd, jnp.broadcast_to(s_target, soc_meas.shape), resid,
            # In degraded mode this is the mean of the *bridged* trace (NaN
            # never reaches campus aggregates).
            rack_mean_row, grid_mean_row,
        )
        if degraded:
            # The mask actually applied this interval.
            telem = telem + (on_row,)
        if safemode:
            telem = telem + (sm.mode,)
        carry2 = (
            x_f2, es2, new_u_prev, cmd_target, new_cmd, soc_meas,
            warm2, hstate2, step_idx + 1,
        )
        if fast:
            carry2 = carry2 + (lg,)
        if safemode:
            carry2 = (carry2, sm)
        return carry2, (grid, telem)

    carry0 = (
        state.filter_state, state.ess_state, state.u_prev,
        state.cmd_applied, state.cmd_target, state.soc_ema, state.qp_warm,
        state.health, jnp.asarray(0.0, jnp.float32),
    )
    if fast:
        carry0 = carry0 + (state.last_good,)
        scan_xs = (chunks, on_rows, hw_base, i0_rows)
    elif degraded:
        scan_xs = (chunks, on_rows, hw_chunks)
    else:
        scan_xs = chunks
    if safemode:
        carry0 = (carry0, state.safemode)
    final_carry, (grid_chunks, telem) = jax.lax.scan(interval, carry0, scan_xs)
    if safemode:
        final_carry, sm_f = final_carry
    else:
        sm_f = state.safemode
    if fast:
        last_good2 = final_carry[-1]
        final_carry = final_carry[:-1]
    (x_f, es_f, u_prev, cmd_applied, cmd_target, soc_ema, warm_f, h_f, _) = (
        final_carry
    )
    grid = grid_chunks.reshape((n_ctrl * k,) + rack_power.shape[1:])[:t]
    new_state = PDUState(
        filter_state=x_f, filter_obj=filt, ess_state=es_f, u_prev=u_prev,
        cmd_applied=cmd_applied, cmd_target=cmd_target, soc_ema=soc_ema,
        qp_warm=warm_f, health=h_f,
        ess_online=state.ess_online, last_good=last_good2,
        safemode=sm_f,
    )
    extra = {}
    ti = 6
    if degraded:
        extra["ess_online"] = telem[ti]
        ti += 1
    if safemode:
        extra["safemode_mode"] = telem[ti]
    return grid, new_state, Telemetry(
        soc=telem[0], command=telem[1], target=telem[2], qp_residual=telem[3],
        rack_mean=telem[4].reshape((n_ctrl * k,))[:t],
        grid_mean=telem[5].reshape((n_ctrl * k,))[:t],
        **extra,
    )


class CampusChunk(NamedTuple):
    """Campus aggregates of one conditioned (T, R) chunk (per-unit means)."""

    campus_rack: jax.Array  # (T,) mean unconditioned campus load
    campus_grid: jax.Array  # (T,) mean conditioned campus load
    soc_mean: jax.Array  # (n_ctrl,) fleet-mean SoC per control interval
    max_qp_residual: jax.Array  # () worst QP primal residual in the chunk
    health: jax.Array  # (3,) [mean EFC, max fade, max DoD] at chunk end
    # Fraction of ESS units online per control interval (ones unless the
    # cfg runs degraded_mode) — the honest ramp-budget denominator: a
    # campus passing spec with 30% of units dark is a different claim than
    # one passing at full strength, and this is where that shows.
    ess_online_frac: jax.Array = None
    # Safe-mode supervisor snapshot at chunk end (zeros unless the cfg runs
    # safemode): (6,) [frac_normal, n_passthrough, n_quarantined,
    # entries_total, readmissions_total, worst_resid_streak].
    safemode: jax.Array = None


def condition_campus(
    cfg: PDUConfig,
    state: PDUState,
    rack_power: jax.Array,  # (T, R) per-unit rack traces
    *,
    qp_iters: int = 30,
    use_plan: bool = True,
    ess_online: jax.Array | None = None,
    ess_weight: jax.Array | None = None,
    faults: flt.FaultSchedule | None = None,
    chunk_start: jax.Array | int = 0,
    fault_edge: int = 1,
) -> tuple[PDUState, CampusChunk]:
    """One streaming-campus step: condition a chunk, reduce to aggregates.

    The per-rack grid waveform is reduced to campus means *inside* the same
    computation (XLA fuses the reduction into the conditioning scan), so a
    streaming engine that only needs campus-level compliance never
    materializes the conditioned (T, R) block outside the step.  It is the
    per-chunk step of the fleet's chunk scan (``fleet._scan_chunks``), which
    the campus and region engines share.  ``health`` is the fleet wear
    snapshot at the chunk's end (zeros unless ``cfg.track_health``) — the
    online telemetry a campus operator would chart.
    """
    grid, state2, telem = condition(
        cfg, state, rack_power, qp_iters=qp_iters, use_plan=use_plan,
        ess_online=ess_online, ess_weight=ess_weight,
        faults=faults, chunk_start=chunk_start, fault_edge=fault_edge,
    )
    if cfg.track_health:
        hsnap = hlt.chunk_aggregates(cfg.health, state2.health, cfg.sample_dt)
    else:
        hsnap = jnp.zeros((3,), jnp.float32)
    if cfg.degraded_mode:
        # The raw chunk may carry NaN sensor dropouts; the bridged mean
        # from the conditioning scan is the honest campus-load signal.
        on_frac = jnp.mean(telem.ess_online, axis=1)
    else:
        on_frac = jnp.ones(telem.soc.shape[0], jnp.float32)
    # Means come from inside the conditioning scan (see Telemetry): values
    # are bitwise-identical to reducing the (T, R) blocks here, but the
    # rendered chunk keeps a single consumer (no producer duplication) and
    # a campus-only engine never reads the (T, R) grid block at all.
    if cfg.safemode:
        smsnap = smode.chunk_snapshot(state2.safemode)
    else:
        smsnap = jnp.zeros((6,), jnp.float32)
    return state2, CampusChunk(
        campus_rack=telem.rack_mean,
        campus_grid=telem.grid_mean,
        soc_mean=jnp.mean(telem.soc, axis=1),
        max_qp_residual=jnp.max(telem.qp_residual),
        health=hsnap,
        ess_online_frac=on_frac,
        safemode=smsnap,
    )


def combined_transfer_function(cfg: PDUConfig, f_hz: jax.Array) -> jax.Array:
    """|H_total| = |H_ESS| * |H_LC| (paper Fig. 7)."""
    return ess.transfer_function(cfg.ess_params, f_hz) * filters.transfer_function_rack_to_grid(
        cfg.filter_params, f_hz
    )
