"""Share of the traced window in which no operation ran on the device,
averaged over the chips the cell uses."""
from chipbench import trace as T
from chipbench.metrics import _common as C


def read(ctx):
    lo, hi = ctx.trace.window
    return C.mean_over_devices(
        ctx, lambda dev: 100.0 * (1.0 - T.busy_ns(dev, ctx.trace.window) / (hi - lo)))
