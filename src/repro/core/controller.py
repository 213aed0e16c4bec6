"""Battery lifetime management controller (paper §6, Appendix B).

Two loops:

  * **Outer loop** (minutes; on regime change): selects the SoC target S*.
    Active mode tracks S_mid; storage mode (long idle windows) drops toward
    S_idle, subject to the usable-idle-budget rule: as the idle window
    elapses, the reachable SoC reduction shrinks and the target rises back
    toward S_mid automatically (paper §6 "Outer Loop").

  * **Inner loop** (every 5 s): a receding-horizon convex program (paper
    Eq. 13-17) over H intervals.  We split the corrective current
    i_k = c_k - d_k with c_k, d_k >= 0 so the efficiency-asymmetric SoC
    dynamics (Eq. 14) become linear, yielding a standard box/inequality
    constrained QP.  We solve it with a fixed-iteration OSQP-style ADMM
    written entirely in ``jax.lax`` — jittable, vmappable across racks,
    and ~microseconds per solve (the paper budget is 10 ms on a Pi 5).

The controller command is *power-normalized* like everything else in
``repro.core``: currents are fractions of rated rack power (the DC bus
voltage is regulated constant).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.ess import ESSParams
from repro.kernels import ops
from repro.utils import pytree_dataclass, static_field


def _mm(a, b):
    """Matrix product at full f32 precision on every backend (the CPU's
    default).  A TPU's default rounds f32 operands to bf16: ``K^-1 A'``
    of the plan came out 2.5e-3 off, and the conditioned campus SoC 5.6e-6
    off the CPU's where full precision keeps it within 6e-8."""
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


# --------------------------------------------------------------------------
# Generic small-QP ADMM solver:  min 1/2 x'Px + q'x  s.t.  l <= Ax <= u
# --------------------------------------------------------------------------


class QPSolution(NamedTuple):
    x: jax.Array
    primal_residual: jax.Array
    dual_residual: jax.Array


def solve_qp_admm(
    p_mat: jax.Array,
    q: jax.Array,
    a_mat: jax.Array,
    lo: jax.Array,
    hi: jax.Array,
    *,
    rho: float = 1.0,
    sigma: float = 1e-6,
    iters: int = 250,
) -> QPSolution:
    """OSQP-style ADMM with a pre-factorized KKT system.

    Small dense problems only (n, m ~ tens): we Cholesky-factor
    (P + sigma*I + rho*A'A) once and iterate a fixed number of steps so the
    whole solve is a single XLA loop with no data-dependent control flow.
    """
    n = q.shape[0]
    kkt = p_mat + sigma * jnp.eye(n) + rho * _mm(a_mat.T, a_mat)
    chol = jax.scipy.linalg.cho_factor(kkt)

    def body(carry, _):
        x, z, y = carry
        rhs = sigma * x - q + _mm(a_mat.T, rho * z - y)
        x_new = jax.scipy.linalg.cho_solve(chol, rhs)
        ax = _mm(a_mat, x_new)
        z_new = jnp.clip(ax + y / rho, lo, hi)
        y_new = y + rho * (ax - z_new)
        return (x_new, z_new, y_new), None

    x0 = jnp.zeros_like(q)
    z0 = jnp.clip(_mm(a_mat, x0), lo, hi)
    y0 = jnp.zeros_like(z0)
    (x, z, y), _ = jax.lax.scan(body, (x0, z0, y0), None, length=iters)
    ax = _mm(a_mat, x)
    primal = jnp.max(jnp.abs(ax - jnp.clip(ax, lo, hi)))
    dual = jnp.max(jnp.abs(_mm(p_mat, x) + q + _mm(a_mat.T, y)))
    return QPSolution(x=x, primal_residual=primal, dual_residual=dual)


# --------------------------------------------------------------------------
# Controller configuration
# --------------------------------------------------------------------------


@pytree_dataclass
class ControllerConfig:
    # Outer loop policy.
    s_mid: jax.Array  # mid-band target during training
    s_idle: jax.Array  # storage-mode target during long idle
    t_enter: jax.Array  # [s] minimum predicted idle to enter storage mode
    delta_s_min: jax.Array  # minimum useful SoC shift to bother
    delta_s_max: jax.Array  # max allowed downward shift
    # Inner loop.
    horizon: int = static_field(default=12)
    dt: jax.Array = None  # control interval [s], default 5 s
    i_max: jax.Array = None  # max corrective current (fraction of rated power)
    deadband: jax.Array = None  # epsilon: |S - S*| below which current = 0
    lam_i: jax.Array = None  # maintenance-current magnitude weight
    lam_delta: jax.Array = None  # command smoothness weight
    lam_term: jax.Array = None  # terminal tracking weight
    meas_tau: jax.Array = None  # BMS SoC measurement EMA time constant [s]
    # Health-aware outer loop: scales the storage-mode excursion with the
    # battery's consumed cycle life (0.0 = off, bit-identical to the
    # wear-blind policy).
    wear_gain: jax.Array = None

    @staticmethod
    def create(
        s_mid: float = 0.5,
        s_idle: float = 0.3,
        t_enter: float = 1800.0,
        delta_s_min: float = 0.05,
        delta_s_max: float = 0.25,
        horizon: int = 12,
        dt: float = 5.0,
        i_max: float = 5e-3,
        deadband: float = 5e-3,
        lam_i: float = 1e-2,
        lam_delta: float = 1e-1,
        lam_term: float = 4.0,
        meas_tau: float = 60.0,
        wear_gain: float = 0.0,
    ) -> "ControllerConfig":
        f = lambda v: jnp.asarray(v, jnp.float32)
        return ControllerConfig(
            s_mid=f(s_mid),
            s_idle=f(s_idle),
            t_enter=f(t_enter),
            delta_s_min=f(delta_s_min),
            delta_s_max=f(delta_s_max),
            horizon=int(horizon),
            dt=f(dt),
            i_max=f(i_max),
            deadband=f(deadband),
            lam_i=f(lam_i),
            lam_delta=f(lam_delta),
            lam_term=f(lam_term),
            meas_tau=f(meas_tau),
            wear_gain=f(wear_gain),
        )


# --------------------------------------------------------------------------
# Outer loop: SoC target selection (paper §6, Eq. 11)
# --------------------------------------------------------------------------


def select_target(
    cfg: ControllerConfig,
    ess: ESSParams,
    idle_remaining_s: jax.Array,
    wear: jax.Array | float = 0.0,
) -> jax.Array:
    """Target S* given the predicted remaining idle time.

    Active mode (idle_remaining < t_enter): S* = S_mid.
    Storage mode: drop toward S_idle, bounded by Eq. 11 and by the usable
    idle budget — the time left minus the time needed to charge back to
    S_mid at the maximum corrective rate.  When the budget can no longer
    cover the return charge, the target reverts to S_mid.

    ``wear`` is the battery's consumed cycle-life fraction (per rack; see
    ``core.health.cycle_life_fraction``).  With ``cfg.wear_gain > 0`` the
    allowed storage-mode excursion shrinks as cycle damage accumulates —
    an aging battery is cycled progressively shallower, the paper's
    "maximize lifetime" knob.  A negative gain *widens* the excursion
    instead (calendar-dominated installs that want to park lower for
    longer).  ``wear_gain = 0`` (default) multiplies the excursion by
    exactly 1.0, so the wear-blind policy is reproduced bit-for-bit.
    """
    # Max SoC rate of change at the corrective current limit.
    charge_rate = cfg.i_max * ess.eta_c / ess.q_max  # [1/s] charging
    discharge_rate = cfg.i_max / (ess.eta_d * ess.q_max)  # [1/s] discharging

    # Eq. 11 floor, with the wear-scaled excursion.
    delta_s_eff = cfg.delta_s_max * jnp.maximum(1.0 - cfg.wear_gain * wear, 0.0)
    s_floor = jnp.maximum(
        jnp.maximum(cfg.s_idle, cfg.s_mid - delta_s_eff), ess.soc_safe_min
    )

    # Usable budget: descend for t_down, return for t_up; t_down+t_up<=idle.
    # With delta = s_mid - target: t_down = delta/discharge_rate,
    # t_up = delta/charge_rate  =>  delta_max_budget solves the equality.
    delta_budget = idle_remaining_s / (1.0 / discharge_rate + 1.0 / charge_rate)
    s_budget = cfg.s_mid - delta_budget

    target = jnp.maximum(s_floor, s_budget)
    useful = (cfg.s_mid - target) >= cfg.delta_s_min
    in_storage = (idle_remaining_s >= cfg.t_enter) & useful
    return jnp.where(in_storage, target, cfg.s_mid)


# --------------------------------------------------------------------------
# Inner loop: receding-horizon QP (paper Eq. 13-17)
# --------------------------------------------------------------------------


def _build_qp(
    cfg: ControllerConfig,
    ess: ESSParams,
    soc_now: jax.Array,
    s_target: jax.Array,
    u_prev: jax.Array,
):
    """Assemble (P, q, A, lo, hi) for variables x = [c_0..c_{H-1}, d_0..d_{H-1}].

    SoC trajectory: S_k = S_0 + (dt/Q) (eta_c * cumsum(c) - cumsum(d)/eta_d),
    normalized error e_k = (S_k - S*) / dS_ref, command u_k = (c_k - d_k)/imax.
    Objective (paper Eq. 13):
        sum_k e_{k+1}^2 + lam_i*(c_k^2 + d_k^2)/imax^2
              + lam_delta*(u_k - u_{k-1})^2  + lam_term * e_H^2.
    (The magnitude penalty on c^2 + d^2 — rather than (c-d)^2 — also
    suppresses the simultaneous charge/discharge "efficiency leak" of the
    split formulation.)
    """
    h = cfg.horizon
    dt = cfg.dt
    # Error normalization (paper Eq. 12).  Floored so a degenerate config
    # (s_mid == s_idle) keeps the QP well-conditioned in float32.
    ds_ref = jnp.maximum(jnp.abs(cfg.s_mid - cfg.s_idle), 0.05)

    # S_{k+1} = S_0 + rows of L @ (eta_c c - d/eta_d) * dt/Q,  L = lower tri ones.
    ltri = jnp.tril(jnp.ones((h, h), jnp.float32))
    g_c = (dt / ess.q_max) * ess.eta_c * ltri  # (h, h): S_{k+1} coeffs on c
    g_d = -(dt / ess.q_max) / ess.eta_d * ltri
    g = jnp.concatenate([g_c, g_d], axis=1)  # (h, 2h): S_{1..H} = S0 + G x

    e0 = (soc_now - s_target) / ds_ref  # scalar offset
    # e_{k+1} = e0 + (G x)_k / ds_ref
    w = jnp.ones((h,), jnp.float32).at[h - 1].add(cfg.lam_term)  # stage + terminal
    ge = g / ds_ref
    p_track = _mm(2.0 * (ge.T * w), ge)
    q_track = _mm(2.0 * ge.T, w * e0)

    # Magnitude penalty lam_i * (c^2 + d^2) / imax^2.
    p_mag = 2.0 * cfg.lam_i / (cfg.i_max**2) * jnp.eye(2 * h)

    # Smoothness on u = (c - d)/imax: D u with first row including u_prev.
    diff = jnp.eye(h, dtype=jnp.float32) - jnp.eye(h, k=-1, dtype=jnp.float32)
    sel = jnp.concatenate([jnp.eye(h), -jnp.eye(h)], axis=1) / cfg.i_max  # u = S x
    dmat = _mm(diff, sel)  # (h, 2h)
    p_smooth = _mm(2.0 * cfg.lam_delta * dmat.T, dmat)
    q_smooth = _mm(
        -2.0 * cfg.lam_delta * dmat.T,
        jnp.eye(h, dtype=jnp.float32)[:, 0] * u_prev,
    )

    p_mat = p_track + p_mag + p_smooth
    q_vec = q_track + q_smooth

    # Constraints: 0 <= c,d <= imax;  soc_safe_min <= S_k <= soc_safe_max.
    a_box = jnp.eye(2 * h)
    lo_box = jnp.zeros((2 * h,))
    hi_box = jnp.full((2 * h,), cfg.i_max)
    a_soc = g
    lo_soc = jnp.full((h,), ess.soc_safe_min) - soc_now
    hi_soc = jnp.full((h,), ess.soc_safe_max) - soc_now
    a_mat = jnp.concatenate([a_box, a_soc], axis=0)
    lo = jnp.concatenate([lo_box, lo_soc])
    hi = jnp.concatenate([hi_box, hi_soc])
    return p_mat, q_vec, a_mat, lo, hi


# --------------------------------------------------------------------------
# Factor-once plan: config-only QP precomputation + batched warm-started ADMM
# --------------------------------------------------------------------------
#
# ``_build_qp`` + ``cho_factor`` depend on the *state* (soc_now, s_target,
# u_prev) only through q, lo, hi — and those are rank-1 updates of fixed
# vectors.  P, A and the ADMM KKT Cholesky factor are pure functions of the
# static config, so at fleet scale (R racks x n_ctrl intervals) rebuilding
# and refactoring them per rack per interval is O(n_ctrl * R * h^3) of
# redundant work.  ``ControllerPlan`` hoists all of it into one
# precomputation; the per-iteration solve then becomes a single
# (2h, 2h) x (2h, R) triangular-solve/matmul pair across the whole rack
# batch, and warm-starting the (x, z, y) iterates across control intervals
# reaches the cold-start residual in ~1/4 the iterations.


class QPWarmState(NamedTuple):
    """ADMM iterates carried across control intervals (warm start).

    Shapes: ``x`` (2h, *batch), ``z``/``y`` (3h, *batch)."""

    x: jax.Array
    z: jax.Array
    y: jax.Array


@pytree_dataclass
class ControllerPlan:
    """Config-only precomputation of the inner-loop QP (factor once).

    ``q = q_e0 * e0 + q_du * u_prev`` with ``e0 = (soc - S*) / ds_ref``;
    ``lo/hi = {lo,hi}_base - soc_rows * soc`` — the only state-dependent
    pieces of the Eq. 13-17 QP.  Everything else, including the ADMM KKT
    Cholesky factor, is shared by every rack and every control interval.
    """

    p_mat: jax.Array  # (2h, 2h) quadratic cost
    a_mat: jax.Array  # (3h, 2h) stacked box + SoC constraints
    kkt_chol: jax.Array  # (2h, 2h) lower Cholesky of P + sigma I + rho A'A
    kkt_inv_sigma: jax.Array  # (2h, 2h) sigma * K^-1 (x-update, x term)
    kkt_inv_at: jax.Array  # (2h, 3h) K^-1 A' (x-update, rho z - y term)
    kkt_inv: jax.Array  # (2h, 2h) K^-1 (x-update, hoisted -K^-1 q term)
    q_e0: jax.Array  # (2h,) dq / d e0
    q_du: jax.Array  # (2h,) dq / d u_prev
    lo_base: jax.Array  # (3h,) constraint lower bounds at soc = 0
    hi_base: jax.Array  # (3h,) constraint upper bounds at soc = 0
    soc_rows: jax.Array  # (3h,) 1.0 on the SoC-constraint rows
    ds_ref: jax.Array  # scalar error normalization (Eq. 12)
    horizon: int = static_field(default=12)
    rho: float = static_field(default=1.0)
    sigma: float = static_field(default=1e-6)


def make_plan(
    cfg: ControllerConfig,
    ess: ESSParams,
    *,
    rho: float = 1.0,
    sigma: float = 1e-6,
) -> ControllerPlan:
    """Precompute the config-only QP pieces (same math as ``_build_qp``).

    Deliberately does NOT share code with ``_build_qp``: the per-step
    assembly is kept as an independent oracle so
    ``tests/test_controller_plan.py`` pins this refactoring against it.
    A change to the QP (Eq. 13-17) must be made in both and the
    equivalence tests re-run."""
    h = cfg.horizon
    dt = cfg.dt
    ds_ref = jnp.maximum(jnp.abs(cfg.s_mid - cfg.s_idle), 0.05)

    ltri = jnp.tril(jnp.ones((h, h), jnp.float32))
    g_c = (dt / ess.q_max) * ess.eta_c * ltri
    g_d = -(dt / ess.q_max) / ess.eta_d * ltri
    g = jnp.concatenate([g_c, g_d], axis=1)  # (h, 2h)

    w = jnp.ones((h,), jnp.float32).at[h - 1].add(cfg.lam_term)
    ge = g / ds_ref
    p_track = _mm(2.0 * (ge.T * w), ge)
    p_mag = 2.0 * cfg.lam_i / (cfg.i_max**2) * jnp.eye(2 * h)
    diff = jnp.eye(h, dtype=jnp.float32) - jnp.eye(h, k=-1, dtype=jnp.float32)
    sel = jnp.concatenate([jnp.eye(h), -jnp.eye(h)], axis=1) / cfg.i_max
    dmat = _mm(diff, sel)
    p_smooth = _mm(2.0 * cfg.lam_delta * dmat.T, dmat)
    p_mat = p_track + p_mag + p_smooth

    q_e0 = _mm(2.0 * ge.T, w)  # q_track = q_e0 * e0
    q_du = -2.0 * cfg.lam_delta * dmat[0]  # q_smooth = q_du * u_prev

    a_mat = jnp.concatenate([jnp.eye(2 * h), g], axis=0)  # (3h, 2h)
    lo_base = jnp.concatenate(
        [jnp.zeros((2 * h,)), jnp.full((h,), ess.soc_safe_min)]
    )
    hi_base = jnp.concatenate(
        [jnp.full((2 * h,), cfg.i_max), jnp.full((h,), ess.soc_safe_max)]
    )
    soc_rows = jnp.concatenate([jnp.zeros((2 * h,)), jnp.ones((h,))])

    kkt = p_mat + sigma * jnp.eye(2 * h) + rho * _mm(a_mat.T, a_mat)
    kkt_chol = jnp.linalg.cholesky(kkt)
    # Explicit K^-1 (tiny, SPD, well-conditioned: P is PSD + sigma I + rho
    # A'A): the ADMM x-update becomes two small GEMMs instead of a pair of
    # LAPACK triangular solves per iteration — at fleet scale the (2h, R)
    # TRSM pair was the single hottest op in the conditioning path.
    kkt_inv = jax.scipy.linalg.cho_solve((kkt_chol, True), jnp.eye(2 * h))
    return ControllerPlan(
        p_mat=p_mat,
        a_mat=a_mat,
        kkt_chol=kkt_chol,
        kkt_inv_sigma=sigma * kkt_inv,
        kkt_inv_at=_mm(kkt_inv, a_mat.T),
        kkt_inv=kkt_inv,
        q_e0=q_e0,
        q_du=q_du,
        lo_base=lo_base,
        hi_base=hi_base,
        soc_rows=soc_rows,
        ds_ref=ds_ref,
        horizon=int(h),
        rho=float(rho),
        sigma=float(sigma),
    )


def _qp_state_terms(
    plan: ControllerPlan,
    soc_now: jax.Array,  # () or (R,)
    s_target: jax.Array,
    u_prev: jax.Array,
):
    """(q, lo, hi) from the state: rank-1 updates of the plan's bases."""
    e0 = (soc_now - s_target) / plan.ds_ref
    if jnp.ndim(e0) > 0:
        soc = jnp.broadcast_to(soc_now, e0.shape)
        u = jnp.broadcast_to(u_prev, e0.shape)
        q = plan.q_e0[:, None] * e0[None, :] + plan.q_du[:, None] * u[None, :]
        lo = plan.lo_base[:, None] - plan.soc_rows[:, None] * soc[None, :]
        hi = plan.hi_base[:, None] - plan.soc_rows[:, None] * soc[None, :]
    else:
        q = plan.q_e0 * e0 + plan.q_du * u_prev
        lo = plan.lo_base - plan.soc_rows * soc_now
        hi = plan.hi_base - plan.soc_rows * soc_now
    return q, lo, hi


def solve_qp_admm_plan(
    plan: ControllerPlan,
    q: jax.Array,  # (2h,) or (2h, R)
    lo: jax.Array,  # (3h,) or (3h, R)
    hi: jax.Array,
    warm: QPWarmState | None = None,
    *,
    iters: int = 30,
) -> tuple[QPSolution, QPWarmState]:
    """Batched ADMM against a prefactorized plan.

    The rack batch rides in the trailing axis: the x-update
    ``x = K^-1 (sigma x - q + A'(rho z - y))`` is evaluated against the
    plan's precomputed ``K^-1`` as two (2h, .) x (., R) GEMMs — with the
    state-only ``K^-1 q`` term hoisted out of the iteration loop — instead
    of a per-iteration pair of batched triangular solves (or R vmapped
    scalar solves).  ``warm`` seeds (x, z, y) from the previous control
    interval; residuals are returned per rack so callers can verify
    matched convergence.
    """
    rho = plan.rho
    a_mat = plan.a_mat
    if warm is None:
        x0 = jnp.zeros_like(q)
        z0 = jnp.clip(_mm(a_mat, x0), lo, hi)
        y0 = jnp.zeros_like(z0)
    else:
        x0, z0, y0 = warm.x, warm.z, warm.y
    kq = _mm(plan.kkt_inv, q)  # state-only: constant across iterations

    # Fused iteration loop (ops.admm_iterate): the stacked x-update GEMM
    # and the structure-exploiting A x (A = [I; G]) — one Pallas kernel on
    # TPU, the jnp reference elsewhere.  The stacked operand is loop-
    # invariant; XLA hoists the concatenate out of the iteration scan.
    kkt_stack = jnp.concatenate([plan.kkt_inv_sigma, plan.kkt_inv_at], axis=1)
    x, z, y = ops.admm_iterate(
        kkt_stack, a_mat[2 * plan.horizon :], kq, lo, hi, x0, z0, y0,
        rho=rho, iters=iters,
    )
    ax = _mm(a_mat, x)
    primal = jnp.max(jnp.abs(ax - jnp.clip(ax, lo, hi)), axis=0)
    dual = jnp.max(jnp.abs(_mm(plan.p_mat, x) + q + _mm(a_mat.T, y)), axis=0)
    return (
        QPSolution(x=x, primal_residual=primal, dual_residual=dual),
        QPWarmState(x=x, z=z, y=y),
    )


def init_warm(
    plan: ControllerPlan | int, batch_shape: tuple[int, ...] = ()
) -> QPWarmState:
    """Zero warm state (== cold start while the SoC is inside the safe band).

    Accepts a plan or a bare horizon, so state containers can allocate the
    warm buffers without building the plan first."""
    h = plan if isinstance(plan, int) else plan.horizon
    return QPWarmState(
        x=jnp.zeros((2 * h,) + tuple(batch_shape), jnp.float32),
        z=jnp.zeros((3 * h,) + tuple(batch_shape), jnp.float32),
        y=jnp.zeros((3 * h,) + tuple(batch_shape), jnp.float32),
    )


def reset_warm_where(warm: QPWarmState, reset: jax.Array) -> QPWarmState:
    """Zero the ADMM iterates of the masked entries (cold start).

    ``reset`` carries the batch shape; it broadcasts against the leading
    iterate axis of each ``(n_iterates, *batch)`` leaf.  Shared by the
    degraded-mode QP admission mask and the safe-mode supervisor, so "this
    rack re-enters with a valid cold start" means the same thing on every
    path.  An all-false mask is bitwise identity.
    """
    keep = ~reset.astype(bool)
    return QPWarmState(
        x=jnp.where(keep, warm.x, 0.0),
        z=jnp.where(keep, warm.z, 0.0),
        y=jnp.where(keep, warm.y, 0.0),
    )


class ControllerOutput(NamedTuple):
    corrective_power: jax.Array  # applied first action (fraction of rated)
    s_target: jax.Array
    in_deadband: jax.Array
    qp_primal_residual: jax.Array


def inner_loop_step(
    cfg: ControllerConfig,
    ess: ESSParams,
    soc_now: jax.Array,
    s_target: jax.Array,
    u_prev: jax.Array,
    *,
    qp_iters: int = 250,
) -> ControllerOutput:
    """One 5-second control step: solve the QP, apply the first action.

    Inside the deadband |S - S*| <= eps the current is forced to zero
    (paper §6: "a narrow margin of error around the target brings the
    current to zero").
    """
    p_mat, q_vec, a_mat, lo, hi = _build_qp(cfg, ess, soc_now, s_target, u_prev)
    sol = solve_qp_admm(p_mat, q_vec, a_mat, lo, hi, iters=qp_iters)
    h = cfg.horizon
    i0 = sol.x[0] - sol.x[h]  # c_0 - d_0
    # Physical saturation: the command is a current limit; ADMM's x iterate
    # may slightly exceed the box before full convergence.
    i0 = jnp.clip(i0, -cfg.i_max, cfg.i_max)
    in_deadband = jnp.abs(soc_now - s_target) <= cfg.deadband
    i0 = jnp.where(in_deadband, 0.0, i0)
    return ControllerOutput(
        corrective_power=i0,
        s_target=s_target,
        in_deadband=in_deadband,
        qp_primal_residual=sol.primal_residual,
    )


def inner_loop_step_plan(
    cfg: ControllerConfig,
    ess: ESSParams,
    plan: ControllerPlan,
    soc_now: jax.Array,  # any batch shape (trailing rack axes), or scalar
    s_target: jax.Array,
    u_prev: jax.Array,
    warm: QPWarmState | None = None,
    *,
    qp_iters: int = 30,
    active: jax.Array | None = None,
) -> tuple[ControllerOutput, QPWarmState]:
    """Factor-free batched control step against a precomputed plan.

    Same semantics as ``inner_loop_step`` (first action, physical clip,
    deadband), but the QP assembly is two rank-1 updates, the solve is
    batched over every rack at once, and the returned ``QPWarmState`` seeds
    the next control interval.

    ``active`` masks degraded racks whose ESS unit is offline: their
    command and reported residual are zeroed and — critically for
    warm-started operation — their warm iterates are reset, so a unit that
    trips and later recovers re-enters with a valid cold start rather than
    ADMM iterates frozen from the pre-fault problem.  ``active=None`` is
    bitwise identical to the unmasked step.
    """
    h = plan.horizon
    batch_shape = jnp.shape(soc_now)

    def flat(a):
        return jnp.reshape(a, (a.shape[0], -1)) if batch_shape else a

    def unflat(a):
        return jnp.reshape(a, (a.shape[0],) + batch_shape) if batch_shape else a

    if batch_shape:
        soc = jnp.reshape(soc_now, (-1,))
        tgt = jnp.reshape(jnp.broadcast_to(s_target, batch_shape), (-1,))
        up = jnp.reshape(jnp.broadcast_to(u_prev, batch_shape), (-1,))
    else:
        soc, tgt, up = soc_now, s_target, u_prev
    act = None
    if active is not None:
        act = jnp.broadcast_to(active, batch_shape)
        act = (jnp.reshape(act, (-1,)) if batch_shape else act) > 0

    q, lo, hi = _qp_state_terms(plan, soc, tgt, up)
    w = None if warm is None else QPWarmState(flat(warm.x), flat(warm.z), flat(warm.y))
    sol, w2 = solve_qp_admm_plan(plan, q, lo, hi, w, iters=qp_iters)
    i0 = jnp.clip(sol.x[0] - sol.x[h], -cfg.i_max, cfg.i_max)
    in_deadband = jnp.abs(soc - tgt) <= cfg.deadband
    i0 = jnp.where(in_deadband, 0.0, i0)
    resid = sol.primal_residual
    if act is not None:
        i0 = jnp.where(act, i0, 0.0)
        resid = jnp.where(act, resid, 0.0)
        w2 = reset_warm_where(w2, ~act)

    def back(a):
        return jnp.reshape(a, batch_shape) if batch_shape else a

    out = ControllerOutput(
        corrective_power=back(i0),
        s_target=back(tgt) if batch_shape else s_target,
        in_deadband=back(in_deadband),
        qp_primal_residual=back(resid),
    )
    return out, QPWarmState(x=unflat(w2.x), z=unflat(w2.z), y=unflat(w2.y))


def simulate_soc_management(
    cfg: ControllerConfig,
    ess: ESSParams,
    soc0: jax.Array,
    n_steps: int,
    *,
    idle_remaining_s: jax.Array | float = 0.0,
    drift_power: jax.Array | float = 0.0,
    qp_iters: int = 120,
    warm_start: bool = False,
) -> dict:
    """Closed-loop SoC trajectory under the controller (paper Fig. 12).

    ``drift_power`` models the hardware path's set-point bias / round-trip
    losses as a constant parasitic charge(+)/discharge(-) power.
    The QP plan is factored once outside the scan (the dominant per-step
    cost at the seed); ``warm_start=True`` additionally carries the ADMM
    iterates across intervals.  The Fig. 12 repro defaults to cold starts:
    a fixed-iteration cold solve lands slightly *above* the true optimum's
    command magnitude near the target, and the paper's ~20 min convergence
    matches that regime (a fully-converged solve creeps into the deadband
    ~1.5x slower).  Fleet conditioning (``pdu.condition``), where solver
    throughput actually matters, uses the warm-started path.
    Returns dict of (n_steps,) arrays: soc, command, target.
    """
    idle = jnp.asarray(idle_remaining_s, jnp.float32)
    drift = jnp.asarray(drift_power, jnp.float32)
    plan = make_plan(cfg, ess)

    def body(carry, k):
        soc, u_prev, warm = carry
        idle_left = jnp.maximum(idle - k * cfg.dt, 0.0)
        s_target = select_target(cfg, ess, idle_left)
        out, warm2 = inner_loop_step_plan(
            cfg, ess, plan, soc, s_target, u_prev,
            warm if warm_start else None, qp_iters=qp_iters,
        )
        p_batt = out.corrective_power + drift
        charge = jnp.maximum(p_batt, 0.0)
        discharge = jnp.maximum(-p_batt, 0.0)
        soc_next = soc + (cfg.dt / ess.q_max) * (
            ess.eta_c * charge - discharge / ess.eta_d
        )
        soc_next = jnp.clip(soc_next, ess.soc_safe_min, ess.soc_safe_max)
        u_prev_next = out.corrective_power / cfg.i_max
        return (soc_next, u_prev_next, warm2), (
            soc_next, out.corrective_power, s_target,
        )

    (_, _, _), (soc, cmd, tgt) = jax.lax.scan(
        body,
        (
            jnp.asarray(soc0, jnp.float32),
            jnp.asarray(0.0, jnp.float32),
            init_warm(plan),
        ),
        jnp.arange(n_steps, dtype=jnp.float32),
    )
    return {"soc": soc, "command": cmd, "target": tgt}
