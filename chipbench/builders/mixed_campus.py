"""The heterogeneous campus: training racks cycling model workloads, an
inference-diurnal block, staggered starts, early stops and a scripted
rack-fault cascade.

The assembly follows the program's ``scenario.mixed_campus`` step by step
(the same draws from ``numpy.random.default_rng(seed)`` in the same
order), kept here so that the benchmark's input does not move when the
program changes.  The seed reaches only per-rack columns and the noise
salt: every static size (racks, samples, smoothing width, noise seed) is
the configuration's, so every seed runs the same compiled programs.
"""
from __future__ import annotations

import numpy as np

from chipbench import deploy


def build(config: dict, seed: int) -> deploy.Deployment:
    n = int(config["racks"])
    hz = float(config["sample_hz"])
    duration = float(config["duration_s"])
    mix = config["mix"]
    rng = np.random.default_rng(int(seed) % (1 << 63))
    n_inf = int(round(n * mix["inference_fraction"]))
    n_train = n - n_inf

    templates = [config["workloads"][a] for a in mix["train"]]
    inf = config["workloads"][mix["inference"]]
    cols = {}
    for key in deploy.WORKLOAD_KEYS:
        vals = [templates[i % len(templates)][key] for i in range(n_train)]
        cols[key] = np.asarray(vals + [inf[key]] * n_inf, np.float32)
    cols["diurnal_phase_s"][n_train:] = rng.uniform(0.0, duration, n_inf)
    cols["t_start_s"] = rng.uniform(0.0, mix["stagger_s"], n).astype(np.float32)
    n_stop = int(round(n * mix["stop_fraction"]))
    stop_idx = rng.choice(n, size=n_stop, replace=False)
    lo_s, hi_s = mix["stop_window"]
    cols["t_end_s"][stop_idx] = rng.uniform(lo_s, hi_s, n_stop) * duration
    n_fault = int(round(n * mix["fault_rack_fraction"]))
    if n_fault:
        f0 = duration * mix["fault_at_fraction"]
        lo = int(rng.integers(0, max(n - n_fault, 1)))
        cols["fault_at_s"][lo:lo + n_fault] = f0 + np.linspace(
            0.0, mix["fault_cascade_s"], n_fault, dtype=np.float32)
    cols["fault_duration_s"] = np.full(n, mix["fault_duration_s"], np.float32)
    cols["scale"] = (1.0 + mix["scale_jitter"] * rng.uniform(-1.0, 1.0, n)).astype(
        np.float32)
    total = int(round(duration * hz))
    return deploy.Deployment(
        name=config["name"],
        campuses=(deploy.Campus(cols=cols, salt=deploy.noise_salts(seed, 1)[0]),),
        sample_hz=hz,
        total_samples=total,
        edge_width=deploy.edge_width(mix["edge_time_s"], hz),
        edge_pad=mix["edge_pad"],
        noise_seed=int(mix["noise_seed"]),
        weights=np.ones((1,), np.float32),
        pdu=config["pdu"],
        qp_iters=int(config["qp_iters"]),
    )
