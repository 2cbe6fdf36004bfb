"""Scheduler: share of the window in which the device is idle and the
innermost open program span is a ``repro.batcher.*`` span."""
import spans


def read(ctx):
    split = spans.idle_by_layer(ctx.events, ctx.lo_ns, ctx.hi_ns)
    if split is None:
        return None
    return 100.0 * split.get("batcher", 0) / (ctx.hi_ns - ctx.lo_ns)
