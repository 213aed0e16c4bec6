"""The comparison that decides ``correct``.

Every call of the window is compared with the reference at the same
position of the stream.  Each number is the worst, over the calls, of one
gap between what the program returned and what the reference computes:

* ``rack``: campus mean of the rendered rack power, max |gap| [pu];
* ``grid``: campus mean of the conditioned grid power, max |gap| [pu];
* ``soc``: fleet-mean SoC per interval and the final per-rack SoC and its
  BMS estimate, max |gap|;
* ``plant``: final ESS ramp-filter and LC-filter states, max |gap| [pu];
* ``wear``: the final battery-wear integrals (cycle damage, charge and
  discharge throughput, SoC sums) and the fleet wear snapshot, max |gap|
  of each over the reference's largest magnitude, worst of them;
* ``qp_residual``: the call's worst QP primal residual, |gap| over the
  reference's;
* ``ramp``: the call's max grid ramp, |gap| over the grid's ramp limit;
* ``spectrum``: the call's worst monitored spectral line of the grid
  power, |gap| over the spectral cap alpha;
* regions also ``poi`` (POI conditioned power, max |gap| [pu]), ``swing``
  (POI frequency deviation, max |gap| [Hz]) and ``modes`` (wide-area band
  magnitudes, max |gap| over the band threshold).

Not compared one by one: the corrective commands and the turning-point
leaves of the wear machine (direction, last extremum, half-cycle count).
They switch on thresholds (the controller's deadband, a reversal of the
SoC), so a rounding-level difference of the state can flip one rack's
command or count; what a flip does to the battery and the grid shows in
``soc``, ``plant``, ``grid`` and ``wear``.
"""
from __future__ import annotations

import numpy as np

from chipbench.reference import observers as O

# Wear leaves that integrate the SoC path (compared); the others are
# thresholded (see above).
WEAR_INTEGRALS = ("h_cycle_damage", "h_charge_soc", "h_discharge_soc", "h_soc_sum",
                  "h_soc_sq_sum")


def _gap(a, b) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    if a.shape != b.shape:
        return float("inf")  # an answer of the wrong shape is wrong
    d = np.abs(a - b)
    return float(np.max(d)) if d.size else 0.0


def reference_reports(dep, ref_call: dict, dtype=None) -> dict:
    """The grid-facing reports of one reference call (host float64; the
    control's in its own precision)."""
    dt, grid = dep.dt, dep.pdu["grid"]
    out = {
        "max_ramp": np.asarray([O.max_ramp(x, dt, dtype) for x in ref_call["campus_grid"]]),
        "spec_worst": np.asarray([O.worst_line(x, dt, grid["f_c"], dtype)
                                  for x in ref_call["campus_grid"]]),
    }
    if dep.region:
        w = dep.weights[:, None].astype(np.float64)
        poi = O._lower(np.sum(O._lower(w * ref_call["campus_grid"], dtype), axis=0), dtype)
        out["poi_grid"] = poi
        out["poi_freq_dev"] = O.poi_swing(poi, dt, dep.poi, dtype)
        out["mode_mags"] = O.mode_mags(poi, dt, dep.bands, dtype)
    return out


def numbers(dep, prog: dict, ref_call: dict, ref_reports: dict, wear_snapshot) -> dict:
    """Every compared number of one call."""
    ps, rs = prog["state"], ref_call["state"]
    grid = dep.pdu["grid"]
    out = {
        "rack": _gap(prog["campus_rack"], ref_call["campus_rack"]),
        "grid": _gap(prog["campus_grid"], ref_call["campus_grid"]),
        "soc": max(_gap(prog["soc_mean"], ref_call["soc_mean"]),
                   _gap(ps["soc"], rs["soc"]), _gap(ps["soc_ema"], rs["soc_ema"])),
        "plant": max(_gap(ps["filter_state"], rs["filter_state"]),
                     _gap(ps["g_filter"], rs["g_filter"])),
    }
    if dep.pdu["track_health"]:
        rel = lambda a, b: _gap(a, b) / max(float(np.max(np.abs(b))), 1e-30)
        out["wear"] = max([rel(ps[n], rs[n]) for n in WEAR_INTEGRALS]
                          + [rel(prog["health_trace"][:, i], wear_snapshot[:, i])
                             for i in range(3)])
    out["qp_residual"] = abs(prog["qp_residual"] - ref_call["qp_residual"]) / max(
        ref_call["qp_residual"], 1e-30)
    out["ramp"] = _gap(prog["max_ramp"], ref_reports["max_ramp"]) / float(grid["beta"])
    out["spectrum"] = _gap(prog["spec_worst"], ref_reports["spec_worst"]) / float(grid["alpha"])
    if dep.region:
        out["poi"] = _gap(prog["poi_grid"], ref_reports["poi_grid"])
        out["swing"] = _gap(prog["poi_freq_dev"], ref_reports["poi_freq_dev"])
        thr = np.asarray([b[3] for b in dep.bands], np.float64)
        out["modes"] = float(np.max(np.abs(prog["mode_mags"] - ref_reports["mode_mags"]) / thr))
    return out


def judge(per_call: list, limits: dict) -> tuple:
    """(correct, failed calls, {name: worst over the calls}) against
    ``limits`` ({name: limit}).  A number without a limit, or a non-finite
    one, fails."""
    names = list(per_call[0]) if per_call else []
    worst = {n: max(c[n] for c in per_call) for n in names}
    failed = 0
    for c in per_call:
        if any(not (c[n] <= limits.get(n, -np.inf)) for n in names):
            failed += 1
    correct = bool(per_call) and failed == 0 and all(n in limits for n in names)
    return correct, failed, worst
