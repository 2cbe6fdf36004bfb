"""Kernels: the decode attention kernel's roofline time (bytes of the
live positions, each KV head once, plus q and output; or its FLOPs, if
that bound is larger) over its device time."""
import work

KERNEL = "decode_attention"


def read(ctx):
    t = ctx.kernel_s(KERNEL)
    calls = ctx.calls("decode")
    nbytes = sum(work.decode_attention_bytes(ctx.dims, s[3]) for s in calls)
    flops = sum(work.decode_attention_flops(ctx.dims, s[3]) for s in calls)
    if not t or not nbytes:
        return None
    return 100.0 * work.roofline_s(flops, nbytes, ctx.peak) / t
