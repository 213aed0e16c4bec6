"""Device busy time per call outside the two kernels: render, observers,
wear sums, QP preparation, the POI fold and the facade's own programs."""
from chipbench import trace as T
from chipbench.metrics import _common as C


def read(ctx):
    def rest(dev):
        if ctx.calls == 0:
            return None
        kernels = sum(C.kernel_ns(dev, ctx, n) or 0 for n in (C.MEGAKERNEL, C.ADMM))
        return (T.busy_ns(dev, ctx.trace.window) - kernels) / ctx.calls / 1e6

    return C.mean_over_devices(ctx, rest)
