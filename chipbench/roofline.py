"""Operations and bytes that a kernel's result needs, from the cell's
shapes, and the chip's peaks.

What is counted is the work of the layer's result, whatever implements it:

* Megakernel, one controller interval of k samples over R racks: 44
  operations per rack-sample for the ESS filter, state of charge and LC
  filter, 4 for the command slew, and 25 for the battery-wear fold when the
  configuration tracks health (73 in all).  Bytes: the (k, R) rack trace
  read once; the per-rack state in (g, soc, 3 filter states, 2 slew rows,
  and 6 wear carries) and out (5 + 6); and what the engine consumes per
  sample and per rack: the campus grid mean (k values) and the four SoC
  block sums (4 R).  The (k, R) grid and SoC blocks the kernel writes today
  are not counted.
* Batched ADMM, one controller interval: per rack and iteration
  ``admm_ops_per_iter`` with n = 2h decision variables and m = 3h
  constraints.  Bytes: the plan matrices once, and per rack K^-1 q, the
  bounds and the x, z, y iterates in and out once.
"""
from __future__ import annotations

import json
import os

F32 = 4
PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")

MEGAKERNEL_OPS = {"hardware": 44, "slew": 4, "wear": 25}


def peaks(device_kind: str) -> dict:
    """{"flops": ..., "bytes_per_s": ...} of one chip; an unknown device
    kind is an error, not a default."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)["chips"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add it to {PEAKS_FILE}")
    return table[device_kind]


def megakernel(k: int, r: int, wear: bool) -> tuple:
    """(operations, bytes) of one interval."""
    per = MEGAKERNEL_OPS["hardware"] + MEGAKERNEL_OPS["slew"] + (
        MEGAKERNEL_OPS["wear"] if wear else 0)
    state_in = 5 + 2 + (6 if wear else 0)
    state_out = 5 + (6 if wear else 0)
    sums = 4 if wear else 0
    nbytes = F32 * (k * r + (state_in + state_out + sums) * r + k)
    return per * k * r, nbytes


def admm_ops_per_iter(h: int) -> int:
    """Per rack and iteration: x = [sK^-1 | K^-1 A'] [x; rho z - y] - K^-1 q
    (2n(n + m)), the SoC rows G x of A x (2 (m - n) n), and the vector
    updates of z and y (6m) and of x (2n); n = 2h, m = 3h."""
    n, m = 2 * h, 3 * h
    return 2 * n * (n + m) + 2 * (m - n) * n + 6 * m + 2 * n


def admm(h: int, iters: int, r: int) -> tuple:
    """(operations, bytes) of one interval's solve."""
    n, m = 2 * h, 3 * h
    plan = n * (n + m) + (m - n) * n
    per_rack_in = n + 2 * m + (n + 2 * m)  # K^-1 q, bounds, x z y
    per_rack_out = n + 2 * m
    return admm_ops_per_iter(h) * iters * r, F32 * (plan + (per_rack_in + per_rack_out) * r)


def least_seconds(ops: float, nbytes: float, pk: dict) -> tuple:
    """(seconds, bound) of the least time the chip could take."""
    t_ops, t_mem = ops / pk["flops"], nbytes / pk["bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
