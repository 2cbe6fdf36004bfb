"""Model step: the hybrid decode step's roofline time (the bytes it
needs: weights once, the held experts some live token uses, each live
slot's SSD and conv state read and written back, K and V of the live
positions; or its FLOPs, if that bound is larger; ``work_hybrid``) over
the decode program's device time."""
import work
import work_hybrid


def read(ctx):
    t = ctx.module_s("_decode")
    calls = ctx.calls("decode")
    nbytes = sum(work_hybrid.decode_bytes(ctx.dims, s[3]) for s in calls)
    flops = sum(work_hybrid.decode_flops(ctx.dims, s[3]) for s in calls)
    if not t or not nbytes:
        return None
    return 100.0 * work.roofline_s(flops, nbytes, ctx.peak) / t
