"""Runtime gate: time-weighted mean of the runtime's active units
(one unit a slot), over the slots."""


def read(ctx):
    num = den = 0.0
    for t0, t1, active, _ in ctx.ticks:
        a, b = max(t0, ctx.t_lo), min(t1, ctx.t_hi)
        if b > a:
            num += active * (b - a)
            den += b - a
    return 100.0 * num / den / ctx.slots if den else None
