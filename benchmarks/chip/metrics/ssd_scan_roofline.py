"""Kernels: the Pallas SSD scan's roofline time over the prompts of the
window's prefills (the chunked algorithm's FLOPs at the published chunk,
or the bytes it reads and writes once, whichever bound is larger;
``work_hybrid``) over the kernel's device time."""
import work
import work_hybrid

KERNEL = "ssd_scan"


def read(ctx):
    t = ctx.kernel_s(KERNEL)
    lengths = [s[3] for s in ctx.calls("prefill")]
    flops = sum(work_hybrid.ssd_scan_flops(ctx.dims, n) for n in lengths)
    nbytes = sum(work_hybrid.ssd_scan_bytes(ctx.dims, n) for n in lengths)
    if not t or not flops:
        return None
    return 100.0 * work.roofline_s(flops, nbytes, ctx.peak) / t
