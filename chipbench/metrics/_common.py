"""What the per-layer readers share: per-device means over the traced
window, and the kernels' names as the device trace shows them."""
from __future__ import annotations

from chipbench import trace as T

# Op names of the two Pallas kernels in the trace (the names of their
# jitted wrappers in kernels/pdu_health.py and kernels/admm_step.py).
MEGAKERNEL = "pdu_health_sim"
ADMM = "admm_iterate"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "collective-permute",
               "all-to-all")


def mean_over_devices(ctx, fn):
    vals = [fn(dev) for dev in (ctx.trace.devices[i] for i in ctx.devices)]
    vals = [v for v in vals if v is not None]
    return sum(vals) / len(vals) if vals else None


def kernel_ns(dev, ctx, name):
    """Summed device time of one kernel in the window, None if it never ran."""
    evs = [e - s for n, s, e in T.clip(dev.ops, ctx.trace.window) if n == name]
    return sum(evs) if evs else None


def per_call_ms(ctx, ns):
    return None if ns is None or ctx.calls == 0 else ns / ctx.calls / 1e6
