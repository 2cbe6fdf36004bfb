"""Scheduler: the program's own queue wait, submit to admit
(``queued_ns`` of ``repro.batcher.admit``), 90th percentile over the
admissions that started in the window."""
import numpy as np

import spans


def read(ctx):
    waits = [a["queued_ns"] for n, _, _, a in spans.host_spans(ctx.events)
             if n == "repro.batcher.admit" and "queued_ns" in a]
    return float(np.percentile(waits, 90)) / 1e6 if waits else None
