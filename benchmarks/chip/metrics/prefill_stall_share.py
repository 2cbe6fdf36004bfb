"""Scheduler: share of the window inside prefill and insert calls, which
every live slot waits on."""


def read(ctx):
    t = sum(s[2] - s[1] for s in ctx.spans if s[0] in ("prefill", "insert"))
    return 100.0 * t / (ctx.t_hi - ctx.t_lo)
