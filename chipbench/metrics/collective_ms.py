"""Device time of the cross-chip collectives per call (the POI psum of
core/grid.py); nothing where no collective ran."""
from chipbench import trace as T
from chipbench.metrics import _common as C


def read(ctx):
    def coll(dev):
        ns = [e - s for n, s, e in T.clip(dev.ops, ctx.trace.window)
              if n.startswith(C.COLLECTIVES)]
        return C.per_call_ms(ctx, sum(ns)) if ns else None

    return C.mean_over_devices(ctx, coll)
