"""Model step: model FLOPs of the hybrid's decode steps (the share held
here, live slots at their live lengths; ``work_hybrid``) over the
decode program's device time at the chip's peak."""
import work_hybrid


def read(ctx):
    t = ctx.module_s("_decode")
    flops = sum(work_hybrid.decode_flops(ctx.dims, s[3])
                for s in ctx.calls("decode"))
    return 100.0 * flops / (t * ctx.peak["flops"]) if t and flops else None
