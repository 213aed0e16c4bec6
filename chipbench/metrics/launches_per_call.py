"""Program executions on the device per call, from the trace's module
events: the engine's scanned program plus the facade's eager glue."""
from chipbench import trace as T
from chipbench.metrics import _common as C


def read(ctx):
    return C.mean_over_devices(
        ctx, lambda dev: (len(T.clip(dev.modules, ctx.trace.window)) / ctx.calls
                          if ctx.calls else None))
