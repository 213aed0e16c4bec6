"""A deployment as plain data: what a builder makes from a configuration
file and a seed, and what both the program and the reference are given.

Nothing here imports the program.  ``program.py`` wraps a deployment into
the program's own types; ``reference/`` reads it as it is.
"""
from __future__ import annotations

import dataclasses

import numpy as np

# The per-rack workload knobs, in the order of the program's
# ``WorkloadParams`` (power/scenario.py).  A campus is one float32 column
# of length R per knob.
WORKLOAD_KEYS = (
    "iteration_period_s", "comm_fraction", "p_compute", "p_comm",
    "dip_period_s", "dip_duration_s", "p_dip", "warmup_s", "p_idle",
    "t_start_s", "t_end_s", "fault_at_s", "fault_duration_s", "p_fault",
    "diurnal_period_s", "diurnal_amp", "diurnal_phase_s", "scale",
    "noise_std",
)
NEVER = 1e30  # an event time that never comes


@dataclasses.dataclass(frozen=True)
class Campus:
    cols: dict  # knob -> (R,) float32
    salt: int  # uint32 measurement-noise salt

    @property
    def n_racks(self) -> int:
        return int(next(iter(self.cols.values())).shape[0])


@dataclasses.dataclass(frozen=True)
class Deployment:
    """One configuration made concrete for one seed.

    ``pdu`` holds the rack rating, grid spec, PDU sizing knobs, controller,
    battery-health and ESS constants, all as plain numbers.  ``region`` is
    true where the campuses are aggregated at one point of
    interconnection (``poi`` and ``bands`` then apply).
    """

    name: str
    campuses: tuple
    sample_hz: float
    total_samples: int
    edge_width: int
    edge_pad: str
    noise_seed: int
    weights: np.ndarray  # (C,) float32 POI shares
    pdu: dict
    qp_iters: int
    region: bool = False
    poi: dict = None
    bands: tuple = ()

    @property
    def dt(self) -> float:
        return 1.0 / self.sample_hz

    @property
    def k(self) -> int:
        """Samples per controller interval."""
        return max(int(round(float(self.pdu["controller"]["dt"]) * self.sample_hz)), 1)

    @property
    def n_racks(self) -> int:
        return sum(c.n_racks for c in self.campuses)


def columns(template: dict, n: int) -> dict:
    """(n,) float32 columns of one workload template."""
    missing = set(WORKLOAD_KEYS) - set(template)
    if missing:
        raise ValueError(f"workload template lacks {sorted(missing)}")
    return {k: np.full((n,), template[k], np.float32) for k in WORKLOAD_KEYS}


def edge_width(edge_time_s: float, sample_hz: float) -> int:
    """Smoothing window in samples (0 = off), as the scenario defines it."""
    return max(int(round(edge_time_s * sample_hz)), 1) if edge_time_s > 0 else 0


def noise_salts(seed: int, n: int) -> list:
    """n uint32 noise salts drawn from the seed alone."""
    rng = np.random.default_rng([int(seed) % (1 << 63), 1])
    return [int(s) for s in rng.integers(0, 1 << 32, size=n, dtype=np.uint64)]
