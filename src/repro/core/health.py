"""Battery health telemetry: online cycle counting + aging (paper §2, §6).

The paper's lifetime claim — "a software system continually monitors the
energy storage system to maximize its lifetime in the presence of frequent
charge/discharge cycles" — needs an *online* wear model: the per-iteration
workload cycling that EasyRider absorbs (sub-second, shallow) and the
storage-mode excursions the outer loop commands (minutes, deep) stress the
battery in completely different ways, and a post-hoc rainflow pass over an
unbounded campus stream is exactly the kind of whole-trace analysis the
streaming engines exist to avoid.

This module keeps all wear telemetry in a constant-size ``HealthState``
that rides the conditioning scan (one per rack, batched):

  * **Half-cycle counter** — a scan-carried turning-point state machine
    (last extremum, current direction): every SoC direction reversal closes
    a half-cycle of depth ``|extremum - previous extremum|``.  On
    monotone-segment traces (sawtooth / iteration waves) this is exactly
    the rainflow half-cycle count; nested-hysteresis traces split large
    cycles at interior reversals (conservative: small cycles are never
    merged away, and with ``kappa > 1`` splitting under-counts damage of
    the enclosing deep cycle, so pair it with the throughput EFC below).
  * **Throughput accumulators** — charge/discharge SoC movement summed per
    branch: equivalent full cycles and (via the efficiency split of
    ``ess.battery_power_from_soc_delta``) the terminal-side energy a BMS
    coulomb counter would report.
  * **SoC-stress + calendar accumulators** — running sums of SoC and SoC^2
    (mean / variance of the operating point) feeding a linear SoC-weighted
    calendar-aging model.

Damage model (equivalent-full-cycle Wöhler form): a half-cycle of depth
``d`` at mid-SoC ``m`` consumes ``0.5 * w(m) * d**kappa / n_cycles_ref`` of
cycle life, with ``w(m) = max(1 + soc_stress_gain*(m - soc_ref), 0)`` —
cycling high in the SoC window wears faster.  Calendar life drains at rate
``(1 + cal_soc_gain*(soc - soc_ref)) / calendar_life_s``.  Capacity fade is
``eol_fade`` at combined damage 1; projected lifetime extrapolates the
observed damage rate.

Chunk-invariance contract: the cycle counter folds sample-by-sample inside
a ``lax.scan`` whose carry is the state (bit-identical under ANY split of
the SoC stream); the throughput/stress integrals fold one block reduction
per ``update`` call — and every conditioning path calls ``update`` exactly
once per controller interval, so the scanned and one-shot engines
(and resumed streams) produce bitwise-equal ``HealthState``s by
construction.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import ess
from repro.utils import pytree_dataclass, static_field


@pytree_dataclass
class HealthParams:
    """Aging-model constants (per-unit SoC domain; times in seconds)."""

    n_cycles_ref: jax.Array  # cycle life at 100% DoD, w = 1 (full cycles)
    soc_stress_gain: jax.Array  # cycle-wear slope vs mid-SoC
    cal_soc_gain: jax.Array  # calendar-wear slope vs SoC
    soc_ref: jax.Array  # reference SoC for both stress weights
    calendar_life_s: jax.Array  # calendar life at soc_ref [s]
    eol_fade: jax.Array  # capacity-fade fraction at end of life
    rest_eps: jax.Array  # SoC hysteresis below which movement is "rest"
    kappa: float = static_field(default=2.0)  # Wöhler DoD exponent

    @staticmethod
    def create(
        n_cycles_ref: float = 4000.0,
        soc_stress_gain: float = 0.6,
        cal_soc_gain: float = 0.8,
        soc_ref: float = 0.5,
        calendar_life_years: float = 12.0,
        eol_fade: float = 0.2,
        rest_eps: float = 0.0,
        kappa: float = 2.0,
    ) -> "HealthParams":
        f = lambda v: jnp.asarray(v, jnp.float32)
        return HealthParams(
            n_cycles_ref=f(n_cycles_ref),
            soc_stress_gain=f(soc_stress_gain),
            cal_soc_gain=f(cal_soc_gain),
            soc_ref=f(soc_ref),
            calendar_life_s=f(calendar_life_years * 365.25 * 86400.0),
            eol_fade=f(eol_fade),
            rest_eps=f(rest_eps),
            kappa=float(kappa),
        )


class HealthState(NamedTuple):
    """Constant-size wear telemetry carried across samples/chunks/resumes.

    All leaves broadcast over leading rack dimensions.  ``samples`` is an
    exact integer count.
    """

    prev_soc: jax.Array  # last SoC sample seen
    last_ext: jax.Array  # SoC at the last direction reversal
    direction: jax.Array  # +1 rising / -1 falling / 0 not yet moved
    half_cycles: jax.Array  # closed half-cycle count
    cycle_damage: jax.Array  # sum of 0.5 * w(mid) * depth**kappa
    max_dod: jax.Array  # deepest closed half-cycle
    charge_soc: jax.Array  # sum of positive SoC steps (capacity fractions)
    discharge_soc: jax.Array  # sum of negative SoC steps (magnitudes)
    soc_sum: jax.Array  # running sum of SoC samples
    soc_sq_sum: jax.Array  # running sum of SoC^2 samples
    samples: jax.Array  # int32 samples observed


def init_state(
    soc0: jax.Array | float = 0.5, batch_shape: tuple[int, ...] | None = None
) -> HealthState:
    s0 = jnp.asarray(soc0, jnp.float32)
    if batch_shape is not None:
        s0 = jnp.broadcast_to(s0, batch_shape)
    # One allocation per leaf: the engines donate the whole state, and
    # donating the same buffer twice (aliased leaves) is an XLA error.
    z = lambda: jnp.zeros(jnp.shape(s0), jnp.float32)
    return HealthState(
        prev_soc=s0,
        last_ext=jnp.array(s0, copy=True),
        direction=z(),
        half_cycles=z(),
        cycle_damage=z(),
        max_dod=z(),
        charge_soc=z(),
        discharge_soc=z(),
        soc_sum=z(),
        soc_sq_sum=z(),
        samples=jnp.zeros(jnp.shape(s0), jnp.int32),
    )


def reinit_where(
    state: HealthState, mask: jax.Array, soc0: jax.Array | float
) -> HealthState:
    """Reset the masked racks' wear telemetry to a fresh history at ``soc0``.

    The safe-mode sanitizer uses this when quarantining a corrupted rack:
    its accumulators are unrecoverable (any of them may be the non-finite
    leaf), so the honest telemetry is "history restarted here" — the
    quarantine counter records that the restart happened.  An all-false
    mask is bitwise identity.
    """
    mask = mask.astype(bool)
    s0 = jnp.broadcast_to(jnp.asarray(soc0, jnp.float32), state.prev_soc.shape)
    pick = lambda new, old: jnp.where(mask, new, old)
    zf = jnp.zeros_like(state.direction)
    return HealthState(
        prev_soc=pick(s0, state.prev_soc),
        last_ext=pick(s0, state.last_ext),
        direction=pick(zf, state.direction),
        half_cycles=pick(zf, state.half_cycles),
        cycle_damage=pick(zf, state.cycle_damage),
        max_dod=pick(zf, state.max_dod),
        charge_soc=pick(zf, state.charge_soc),
        discharge_soc=pick(zf, state.discharge_soc),
        soc_sum=pick(zf, state.soc_sum),
        soc_sq_sum=pick(zf, state.soc_sq_sum),
        samples=pick(jnp.zeros_like(state.samples), state.samples),
    )


def _pow_depth(depth: jax.Array, kappa: float) -> jax.Array:
    """depth**kappa with a cheap repeated-multiply path for integer kappa
    (the scan body evaluates this every sample; ``jnp.power`` is the single
    most expensive op it could contain)."""
    if float(kappa) == 1.0:
        return depth
    if float(kappa).is_integer() and 2 <= int(kappa) <= 4:
        out = depth
        for _ in range(int(kappa) - 1):
            out = out * depth
        return out
    return jnp.power(depth, kappa)


def step_consts(p: HealthParams) -> tuple:
    """(c0, c1, rest_eps, kappa) for ``update_consts``, with the mid-SoC
    stress weight constants folded:
    ``0.5 * max(1 + g*(0.5*(prev+ext) - ref), 0) == max(c0 + c1*(prev+ext), 0)``.

    Computed as host floats (params must be concrete — the same convention
    ``pdu.condition`` applies to ``ESSParams``), so the conditioning path
    bakes them into its compiled step.
    """
    g = float(p.soc_stress_gain)
    ref = float(p.soc_ref)
    return 0.5 * (1.0 - g * ref), 0.25 * g, float(p.rest_eps), p.kappa


def update_consts(
    consts: tuple, state: HealthState, soc: jax.Array
) -> HealthState:
    """Fold one (T, ...) block of SoC samples with prebaked ``step_consts``.

    Hybrid fold, the profiled optimum at fleet width: only the genuinely
    sequential turning-point machine rides a ``lax.scan`` (5 small
    carries — a fatter scan spills the CPU loop's L1 working set), while
    the throughput/stress integrals are vectorized block reductions.
    Consequence for reproducibility: the scan-carried leaves (extremum,
    direction, half-cycle count, cycle damage, max DoD) are bit-identical
    under ANY split of the stream; the reduction leaves (charge/discharge/
    SoC sums) are bit-identical under any split into the SAME blocks — and
    every conditioning path folds exactly one controller interval per
    block, so the scanned and one-shot engines agree bitwise on the whole
    state.
    """
    c0, c1, eps, kappa = consts
    prev_t = jnp.concatenate([state.prev_soc[None], soc[:-1]], axis=0)
    delta = soc - prev_t
    step_dir = jnp.where(
        delta > eps, 1.0, jnp.where(delta < -eps, -1.0, 0.0)
    )

    def body(carry, inp):
        last_ext, direction, half_cycles, damage, max_dod = carry
        prev, sd = inp
        # A reversal: the new movement opposes the established direction.
        rev = (sd * direction) < 0.0
        revf = jnp.where(rev, 1.0, 0.0)
        depth = jnp.abs(prev - last_ext)
        half_w = jnp.maximum(c0 + c1 * (prev + last_ext), 0.0)
        dmg = half_w * _pow_depth(depth, kappa)
        return (
            jnp.where(rev, prev, last_ext),
            jnp.where(sd != 0.0, sd, direction),
            half_cycles + revf,
            damage + revf * dmg,
            jnp.maximum(max_dod, revf * depth),
        ), None

    (last_ext, direction, half_cycles, damage, max_dod), _ = jax.lax.scan(
        body,
        (state.last_ext, state.direction, state.half_cycles,
         state.cycle_damage, state.max_dod),
        (prev_t, step_dir),
    )
    return HealthState(
        prev_soc=soc[-1],
        last_ext=last_ext,
        direction=direction,
        half_cycles=half_cycles,
        cycle_damage=damage,
        max_dod=max_dod,
        charge_soc=state.charge_soc + jnp.sum(jnp.maximum(delta, 0.0), axis=0),
        discharge_soc=state.discharge_soc
        + jnp.sum(jnp.maximum(-delta, 0.0), axis=0),
        soc_sum=state.soc_sum + jnp.sum(soc, axis=0),
        soc_sq_sum=state.soc_sq_sum + jnp.sum(soc * soc, axis=0),
        samples=state.samples + jnp.int32(soc.shape[0]),
    )


def update(
    p: HealthParams,
    state: HealthState,
    soc: jax.Array,  # (T, ...) SoC trace block
    dt: float,
) -> HealthState:
    """Fold one block of SoC samples into the health state.

    ``dt`` is only used for the integer sample count; time integrals are
    scaled in the derived reports, so a block can be folded before its
    dt-dependent interpretation is fixed.
    """
    del dt  # time-scaling lives in the derived reports (samples * dt)
    return update_consts(step_consts(p), state, soc)


# ------------------------------------------------------------------ derived


def elapsed_seconds(state: HealthState, dt: float) -> jax.Array:
    return state.samples.astype(jnp.float32) * dt


def equivalent_full_cycles(state: HealthState) -> jax.Array:
    """Throughput EFC: total |dSoC| / 2 (one EFC = one full charge+discharge)."""
    return 0.5 * (state.charge_soc + state.discharge_soc)


def terminal_throughput_s(ep: ess.ESSParams, state: HealthState) -> jax.Array:
    """Terminal-side energy throughput [s * P_RATED]: what a BMS coulomb
    counter sees, via the branch split of ``ess.battery_power_from_soc_delta``
    (charging draws 1/eta_c per unit stored; discharging delivers eta_d)."""
    return ep.q_max * (state.charge_soc / ep.eta_c + state.discharge_soc * ep.eta_d)


def cycle_life_fraction(p: HealthParams, state: HealthState) -> jax.Array:
    """Fraction of cycle life consumed (the controller's wear signal)."""
    return state.cycle_damage / p.n_cycles_ref


def calendar_life_fraction(
    p: HealthParams, state: HealthState, dt: float
) -> jax.Array:
    """Fraction of calendar life consumed, SoC-weighted.

    The linear stress factor ``1 + g*(soc - soc_ref)`` integrates to a
    closed form of the additive accumulators — no per-sample exp needed:
    ``integral = elapsed + g * (soc_sum*dt - soc_ref * elapsed)``.
    """
    t = elapsed_seconds(state, dt)
    stress_t = t + p.cal_soc_gain * (state.soc_sum * dt - p.soc_ref * t)
    return jnp.maximum(stress_t, 0.0) / p.calendar_life_s


def capacity_fade(p: HealthParams, state: HealthState, dt: float) -> jax.Array:
    """Capacity-fade fraction: ``eol_fade`` at combined damage 1."""
    frac = cycle_life_fraction(p, state) + calendar_life_fraction(p, state, dt)
    return p.eol_fade * frac


def projected_lifetime_s(
    p: HealthParams, state: HealthState, dt: float
) -> jax.Array:
    """Extrapolated time to end of life at the observed damage rate."""
    t = elapsed_seconds(state, dt)
    frac = cycle_life_fraction(p, state) + calendar_life_fraction(p, state, dt)
    return jnp.where(frac > 0.0, t / jnp.maximum(frac, 1e-30), jnp.inf)


class HealthReport(NamedTuple):
    """Derived per-rack wear report (leaves broadcast over rack dims)."""

    efc: jax.Array  # equivalent full cycles (throughput)
    half_cycles: jax.Array
    max_dod: jax.Array
    throughput_s: jax.Array  # terminal energy throughput [s * P_RATED]
    cycle_life_frac: jax.Array
    calendar_life_frac: jax.Array
    capacity_fade: jax.Array
    projected_life_s: jax.Array
    mean_soc: jax.Array
    soc_std: jax.Array
    elapsed_s: jax.Array


def report(
    p: HealthParams, ep: ess.ESSParams, state: HealthState, dt: float
) -> HealthReport:
    n = jnp.maximum(state.samples.astype(jnp.float32), 1.0)
    mean = state.soc_sum / n
    var = jnp.maximum(state.soc_sq_sum / n - mean * mean, 0.0)
    return HealthReport(
        efc=equivalent_full_cycles(state),
        half_cycles=state.half_cycles,
        max_dod=state.max_dod,
        throughput_s=terminal_throughput_s(ep, state),
        cycle_life_frac=cycle_life_fraction(p, state),
        calendar_life_frac=calendar_life_fraction(p, state, dt),
        capacity_fade=capacity_fade(p, state, dt),
        projected_life_s=projected_lifetime_s(p, state, dt),
        mean_soc=mean,
        soc_std=jnp.sqrt(var),
        elapsed_s=elapsed_seconds(state, dt),
    )


def fleet_summary(rep: HealthReport, *, json_safe: bool = False) -> dict:
    """Campus-level headline numbers from a per-rack report (host floats).

    An empty wear history projects an INFINITE lifetime, and ``float('inf')``
    is not valid JSON — ``json.dumps`` emits the non-standard ``Infinity``
    literal that strict parsers (and most log pipelines) reject.  With
    ``json_safe=True`` every non-finite value is clamped to ``None`` (JSON
    null), so the summary always survives
    ``json.dumps(..., allow_nan=False)`` — the operator service's audit log
    writes it this way.
    """
    import math

    import numpy as np

    a = lambda x: np.asarray(x)
    out = {
        "efc_mean": float(a(rep.efc).mean()),
        "efc_max": float(a(rep.efc).max()),
        "half_cycles_mean": float(a(rep.half_cycles).mean()),
        "worst_dod": float(a(rep.max_dod).max()),
        "fade_mean": float(a(rep.capacity_fade).mean()),
        "fade_max": float(a(rep.capacity_fade).max()),
        "projected_life_years_min": float(
            a(rep.projected_life_s).min() / (365.25 * 86400.0)
        ),
        "mean_soc": float(a(rep.mean_soc).mean()),
    }
    if json_safe:
        out = {k: (v if math.isfinite(v) else None) for k, v in out.items()}
    return out


def chunk_aggregates(p: HealthParams, state: HealthState, dt: float) -> jax.Array:
    """(3,) fleet snapshot for streaming telemetry: [mean EFC, max fade,
    max closed-half-cycle DoD].  Cheap enough to evaluate at every chunk."""
    fade = capacity_fade(p, state, dt)
    return jnp.stack(
        [
            jnp.mean(equivalent_full_cycles(state)),
            jnp.max(fade),
            jnp.max(state.max_dod),
        ]
    )
