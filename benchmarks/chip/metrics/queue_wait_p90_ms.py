"""Scheduler: from when a request was due to the start of its prefill,
90th percentile over the prefills that started in the window."""
import numpy as np


def read(ctx):
    waits = [r.prefill_s - r.due_s for r in ctx.reqs
             if r.prefill_s is not None and ctx.t_lo <= r.prefill_s < ctx.t_hi]
    return float(np.percentile(waits, 90)) * 1e3 if waits else None
