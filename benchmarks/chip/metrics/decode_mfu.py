"""Model step: model FLOPs of the decode steps (live slots at their live
lengths) over their device time at the chip's peak."""
import work


def read(ctx):
    t = ctx.module_s("_decode")
    flops = sum(work.decode_step_flops(ctx.dims, s[3])
                for s in ctx.calls("decode"))
    return 100.0 * flops / (t * ctx.peak["flops"]) if t and flops else None
