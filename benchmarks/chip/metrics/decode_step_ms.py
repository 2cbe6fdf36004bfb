"""Engine: device time of the jitted decode program per call."""


def read(ctx):
    t = ctx.module_s("_decode")
    n = len(ctx.calls("decode"))
    return t / n * 1e3 if t and n else None
