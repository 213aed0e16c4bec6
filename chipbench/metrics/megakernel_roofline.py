"""Least time the chip could take for the megakernel's work over the
kernel's device time (roofline.megakernel counts the work)."""
from chipbench import roofline
from chipbench.metrics import _common as C


def read(ctx):
    ops, nbytes = roofline.megakernel(ctx.k, ctx.racks_per_chip, ctx.wear)
    least, _ = roofline.least_seconds(ops, nbytes, ctx.peaks)

    def share(dev):
        ns = C.kernel_ns(dev, ctx, C.MEGAKERNEL)
        if ns is None or ctx.calls == 0:
            return None
        return 100.0 * least * ctx.intervals_per_call * ctx.calls / (ns * 1e-9)

    return C.mean_over_devices(ctx, share)
