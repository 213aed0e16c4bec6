"""The system under test, as the benchmark drives it.

The only module of the benchmark that imports the program (besides the
compile-cache placement in ``run.py``).  It wraps a ``deploy.Deployment``
into the program's scenario, region and PDU types and exposes one call:
condition a window ``[start, stop)`` of the stream from a carried state
through the ``fleet.condition`` facade.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import compliance, controller as ctrl, fleet, grid, health as hlt, pdu, sizing
from repro.power import scenario as SC
from repro.sharding import rules


def _scenario(dep, campus):
    params = SC.WorkloadParams(**{k: jnp.asarray(v) for k, v in campus.cols.items()})
    s = SC.Scenario(
        params=params, seg_bounds=None, seg_powers=None,
        sample_hz=float(dep.sample_hz), total_samples=int(dep.total_samples),
        edge_width=int(dep.edge_width), edge_pad=dep.edge_pad,
        noise_seed=int(dep.noise_seed),
    )
    return SC.with_noise_salt(s, np.uint32(campus.salt))


def pdu_config(dep) -> pdu.PDUConfig:
    p = dep.pdu
    rack = sizing.RackRating(**p["rack"])
    spec = compliance.GridSpec.create(**p["grid"])
    return pdu.make_pdu(
        rack, spec, sample_dt=1.0 / dep.sample_hz,
        f_f_hz=p["f_f_hz"], soc_window=tuple(p["soc_window"]),
        capacity_margin=p["capacity_margin"], ramp_margin=p["ramp_margin"],
        controller_cfg=ctrl.ControllerConfig.create(**p["controller"]),
        health_params=hlt.HealthParams.create(**p["health"]),
        track_health=bool(p["track_health"]),
    )


class System:
    """One deployment, built once in set-up and driven window by window."""

    def __init__(self, dep, devices):
        self.dep = dep
        self.cfg = pdu_config(dep)
        self.spec = compliance.GridSpec.create(**dep.pdu["grid"])
        scens = [_scenario(dep, c) for c in dep.campuses]
        if dep.region:
            poi = grid.POIConfig(**dep.poi)
            bands = tuple(grid.ModeBand(*b) for b in dep.bands)
            self.target = grid.region(
                scens, weights=dep.weights, poi=poi, bands=bands, salt_noise=False)
            self.mesh = rules.region_mesh(len(scens), devices=devices[:len(scens)])
            self.state0 = tuple(self._init_state(s) for s in scens)
        else:
            self.target = scens[0]
            self.mesh = None
            self.state0 = self._init_state(scens[0])

    def _init_state(self, scen):
        r0 = SC.render(scen, 0, 1)[0]
        return pdu.init_state(self.cfg, r0, soc0=float(self.dep.pdu["soc0"]))

    def call(self, state, start: int, stop: int, chunk_intervals: int):
        """Condition ``[start, stop)`` from ``state``; returns the facade's
        ``ConditioningResult`` (its ``.state`` feeds the next call)."""
        return fleet.condition(
            self.target, self.cfg, self.spec, mesh=self.mesh,
            qp_iters=self.dep.qp_iters,
            stream=fleet.StreamOptions(
                chunk_intervals=chunk_intervals, state=state,
                start_sample=start, stop_sample=stop),
        )


def outputs(result, dep) -> dict:
    """The numbers of one call the comparison reads, as host arrays.

    Campus fields carry a leading campus axis (length 1 for a campus);
    ``state`` holds the final per-campus leaves under the reference's
    names.
    """
    c = len(dep.campuses)
    lead = (lambda x: np.asarray(x)[None]) if not dep.region else np.asarray
    out = {
        "campus_rack": lead(result.campus_rack).reshape(c, -1),
        "campus_grid": lead(result.campus_grid).reshape(c, -1),
        "soc_mean": lead(result.soc_mean).reshape(c, -1),
        "qp_residual": float(np.asarray(result.max_qp_residual)),
        "health_trace": lead(result.health_trace).reshape(c, -1, 3)[:, -1],
    }
    per = result.per_campus if dep.region else (result,)
    out["max_ramp"] = np.asarray([float(r.report_grid.max_ramp) for r in per])
    out["spec_worst"] = np.asarray(
        [float(r.report_grid.worst_high_freq_mag) for r in per])
    states = tuple(result.state) if dep.region else (result.state,)
    out["state"] = {
        "filter_state": np.stack([np.asarray(s.filter_state) for s in states]),
        "g_filter": np.stack([np.asarray(s.ess_state.g_filter) for s in states]),
        "soc": np.stack([np.asarray(s.ess_state.soc) for s in states]),
        "soc_ema": np.stack([np.asarray(s.soc_ema) for s in states]),
    }
    for name in hlt.HealthState._fields:
        out["state"]["h_" + name] = np.stack(
            [np.asarray(getattr(s.health, name)) for s in states])
    if dep.region:
        out["poi_grid"] = np.asarray(result.poi_grid)
        out["poi_freq_dev"] = np.asarray(result.poi_freq_dev)
        out["mode_mags"] = np.asarray(result.report_poi.mode_mags)
    return out


def block(result):
    """Wait for every array of a result (the next call consumes its state)."""
    return jax.block_until_ready(
        [x for x in jax.tree_util.tree_leaves(result) if isinstance(x, jax.Array)])
