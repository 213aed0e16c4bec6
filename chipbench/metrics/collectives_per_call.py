"""Cross-chip collective executions per call (the POI psum of
core/grid.py), from the op names ``collective_ms`` reads: one per chunk
where the fold sums each chunk once, one per sample where it sums each
sample.  An asynchronous pair (``<op>-start``, ``<op>-done``) counts once.
Nothing where no collective ran."""
from chipbench import trace as T
from chipbench.metrics import _common as C


def read(ctx):
    def count(dev):
        n = sum(1 for name, _, _ in T.clip(dev.ops, ctx.trace.window)
                if name.startswith(C.COLLECTIVES) and not name.endswith("-done"))
        return n / ctx.calls if n and ctx.calls else None

    return C.mean_over_devices(ctx, count)
