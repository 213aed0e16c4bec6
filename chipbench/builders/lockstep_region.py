"""Campuses that checkpoint in lockstep, aggregated at one point of
interconnection.

Follows the program's ``grid.checkpoint_region`` with ``stagger=False``:
every campus runs the same checkpoint-stall workload (compute plateau, no
communication wave, a stall every ``dip_period_s``), started at t = 0.
The seed reaches only the per-campus measurement-noise salts, so every
seed runs the same compiled programs.
"""
from __future__ import annotations

import numpy as np

from chipbench import deploy


def build(config: dict, seed: int) -> deploy.Deployment:
    n = int(config["racks"])
    c = int(config["campuses"])
    hz = float(config["sample_hz"])
    mix = config["mix"]
    template = config["workloads"][mix["workload"]]
    salts = deploy.noise_salts(seed, c)
    campuses = tuple(
        deploy.Campus(cols=deploy.columns(template, n), salt=salts[i])
        for i in range(c))
    return deploy.Deployment(
        name=config["name"],
        campuses=campuses,
        sample_hz=hz,
        total_samples=int(round(float(config["duration_s"]) * hz)),
        edge_width=deploy.edge_width(mix["edge_time_s"], hz),
        edge_pad=mix["edge_pad"],
        noise_seed=int(mix["noise_seed"]),
        weights=np.full((c,), 1.0 / c, np.float32),
        pdu=config["pdu"],
        qp_iters=int(config["qp_iters"]),
        region=True,
        poi=config["poi"],
        bands=tuple(tuple(b) for b in config["bands"]),
    )
