"""Least time the chip could take for the controller QP's ADMM work over
the kernel's device time (roofline.admm counts the work)."""
from chipbench import roofline
from chipbench.metrics import _common as C


def read(ctx):
    ops, nbytes = roofline.admm(ctx.horizon, ctx.qp_iters, ctx.racks_per_chip)
    least, _ = roofline.least_seconds(ops, nbytes, ctx.peaks)

    def share(dev):
        ns = C.kernel_ns(dev, ctx, C.ADMM)
        if ns is None or ctx.calls == 0:
            return None
        return 100.0 * least * ctx.intervals_per_call * ctx.calls / (ns * 1e-9)

    return C.mean_over_devices(ctx, share)
