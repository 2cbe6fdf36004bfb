"""Device: share of the window in which no operation ran on the chip."""
import devtrace


def read(ctx):
    s = devtrace.idle_share(ctx.events, ctx.lo_ns, ctx.hi_ns)
    return None if s is None else 100.0 * s
