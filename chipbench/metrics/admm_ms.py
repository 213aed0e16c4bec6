"""Device time of the batched ADMM kernel (kernels/admm_step.py) per call."""
from chipbench.metrics import _common as C


def read(ctx):
    return C.mean_over_devices(
        ctx, lambda dev: C.per_call_ms(ctx, C.kernel_ns(dev, ctx, C.ADMM)))
