"""The conditioner's constants, derived from the configuration's stated
design point in float64 (EasyRider, arXiv:2604.15522, Appendix A.1 and
Eqs. 2, 13-17).

* Battery: energy E_B = eps / (gamma * beta_ess) * P_rated, times the
  capacity margin; power limit max(1.25 eps, 1) of rated power; the ESS
  ramp limit is the grid's beta over the ramp margin.
* Passive filter: L-C with cutoff f_f and characteristic impedance a
  quarter of the load impedance, an R-L damping leg (L_da = L_f / 2, R
  chosen on a 160-point log grid to minimise the resonant peak), in per
  unit of the rack's base impedance, discretised exactly (zero-order hold).
* Controller QP over x = [c_0..c_{H-1}, d_0..d_{H-1}]: tracking, magnitude
  and smoothness costs, box and state-of-charge constraints, and the ADMM
  x-update's inverse K^-1 = (P + sigma I + rho A'A)^-1.
"""
from __future__ import annotations

import math

import numpy as np
import scipy.linalg

SIGMA = 1e-6  # ADMM proximal weight
RHO = 1.0  # ADMM penalty


def _filter(rack: dict, f_f_hz: float) -> tuple:
    p, v = float(rack["p_rated_w"]), float(rack["v_dc"])
    z_load = v * v / p
    z0 = z_load / 4.0
    w = 2.0 * math.pi * f_f_hz
    l_f, c_f = z0 / w, 1.0 / (w * z0)
    l_da = 0.5 * l_f
    f0 = 1.0 / (2.0 * math.pi * math.sqrt(l_f * c_f))
    s = 2j * np.pi * np.logspace(math.log10(f0 / 30.0), math.log10(f0 * 30.0), 1200)
    zc, zl = 1.0 / (s * c_f), s * l_f

    def peak(r):
        zd = r + s * l_da
        return float(np.max(np.abs(zc / (zc + zl * zd / (zl + zd)))))

    rs = math.sqrt(l_f / c_f) * np.logspace(-2.0, 2.0, 160)
    r_da = float(rs[int(np.argmin([peak(r) for r in rs]))])
    # Per unit of the base impedance z_load, stored as float32 as the
    # component values are.
    f32 = lambda x: float(np.float32(x))
    return f32(l_f / z_load), f32(c_f * z_load), f32(r_da / z_load), f32(l_da / z_load)


def discrete_filter(rack: dict, f_f_hz: float, dt: float):
    """(Ad, Bd, c) of the damped LC filter; states [i_L, v_C, i_D], inputs
    [v_in, i_load], output the busbar current i_L + i_D."""
    l_f, c_f, r_da, l_da = (float(x) for x in _filter(rack, f_f_hz))
    a = np.array([[0.0, -1.0 / l_f, 0.0],
                  [1.0 / c_f, 0.0, 1.0 / c_f],
                  [0.0, -1.0 / l_da, -r_da / l_da]])
    b = np.array([[1.0 / l_f, 0.0], [0.0, -1.0 / c_f], [1.0 / l_da, 0.0]])
    aug = np.zeros((5, 5))
    aug[:3, :3], aug[:3, 3:] = a, b
    e = scipy.linalg.expm(aug * dt)
    return e[:3, :3], e[:3, 3:], np.array([1.0, 0.0, 1.0])


def ess(rack: dict, grid: dict, cfg: dict) -> dict:
    eps = (rack["p_rated_w"] - rack["p_min_w"]) / rack["p_rated_w"]
    beta = float(grid["beta"]) / cfg["ramp_margin"]
    lo, hi = cfg["soc_window"]
    e_b = eps / ((hi - lo) * beta) * rack["p_rated_w"]
    f32 = lambda x: float(np.float32(x))
    return {
        "beta": f32(beta),
        "q_max": f32(cfg["capacity_margin"] * e_b / rack["p_rated_w"]),
        "eta_c": f32(cfg["ess"]["eta_c"]),
        "eta_d": f32(cfg["ess"]["eta_d"]),
        "p_max": f32(max(eps * 1.25, 1.0)),
        "soc_min": f32(lo),
        "soc_max": f32(hi),
    }


def qp(ctrl: dict, e: dict) -> dict:
    """Config-only pieces of the controller QP, in float64."""
    h = int(ctrl["horizon"])
    dt, q = float(ctrl["dt"]), e["q_max"]
    ds_ref = max(abs(ctrl["s_mid"] - ctrl["s_idle"]), 0.05)
    ltri = np.tril(np.ones((h, h)))
    g = np.concatenate([(dt / q) * e["eta_c"] * ltri, -(dt / q) / e["eta_d"] * ltri], axis=1)
    w = np.ones(h)
    w[-1] += ctrl["lam_term"]
    ge = g / ds_ref
    imax = ctrl["i_max"]
    diff = np.eye(h) - np.eye(h, k=-1)
    dmat = diff @ (np.concatenate([np.eye(h), -np.eye(h)], axis=1) / imax)
    p_mat = (2.0 * (ge.T * w) @ ge + 2.0 * ctrl["lam_i"] / imax ** 2 * np.eye(2 * h)
             + 2.0 * ctrl["lam_delta"] * dmat.T @ dmat)
    a_mat = np.concatenate([np.eye(2 * h), g], axis=0)
    kinv = np.linalg.inv(p_mat + SIGMA * np.eye(2 * h) + RHO * a_mat.T @ a_mat)
    return {
        "h": h,
        "a": a_mat,
        "kinv": kinv,
        "kinv_at": kinv @ a_mat.T,
        "q_e0": 2.0 * ge.T @ w,
        "q_du": -2.0 * ctrl["lam_delta"] * dmat[0],
        "lo": np.concatenate([np.zeros(2 * h), np.full(h, e["soc_min"])]),
        "hi": np.concatenate([np.full(2 * h, imax), np.full(h, e["soc_max"])]),
        "soc_rows": np.concatenate([np.zeros(2 * h), np.ones(h)]),
        "ds_ref": ds_ref,
    }


def health(cfg: dict) -> dict:
    """Battery-wear constants: a half-cycle of depth d between extrema a
    and b costs max(c0 + c1 (a + b), 0) d^kappa."""
    g, ref = cfg["soc_stress_gain"], cfg["soc_ref"]
    return {
        "c0": 0.5 * (1.0 - g * ref),
        "c1": 0.25 * g,
        "eps": float(cfg["rest_eps"]),
        "kappa": float(cfg["kappa"]),
        "n_cycles_ref": float(cfg["n_cycles_ref"]),
        "cal_soc_gain": float(cfg["cal_soc_gain"]),
        "soc_ref": float(ref),
        "calendar_life_s": float(cfg["calendar_life_years"]) * 365.25 * 86400.0,
        "eol_fade": float(cfg["eol_fade"]),
    }
