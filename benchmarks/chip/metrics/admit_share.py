"""Scheduler: share of the window inside ``repro.batcher.admit`` (upload,
prefill, insert and first-token readback), which every live slot waits
on."""
import spans


def read(ctx):
    host = spans.host_spans(ctx.events)
    if not host:
        return None
    admits = [(a, b) for n, a, b, _ in host if n == "repro.batcher.admit"]
    return 100.0 * spans.union_ns(admits, ctx.lo_ns, ctx.hi_ns) / (
        ctx.hi_ns - ctx.lo_ns)
