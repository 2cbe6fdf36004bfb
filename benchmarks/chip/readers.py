"""What a per-layer metric reader sees, and how readers are found.

Each per-layer metric named in ``BENCHMARK.json`` has a file in
``metrics/`` (see :func:`reader`) with a function ``read(ctx) -> float |
None``. A reader that finds nothing to read returns ``None`` and the
metric is left out of the result line. Everything here is over the traced window
of a ``--trace 1`` run.
"""
from __future__ import annotations

import importlib.util
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

import devtrace
from harness import HERE


@dataclass
class Context:
    events: list               # devtrace events inside the traced window
    lo_ns: int
    hi_ns: int
    t_lo: float                # the same window on the harness's clock
    t_hi: float
    spans: List[tuple]         # (name, t0, t1, info) inside the window
    reqs: list
    ticks: List[tuple]         # (t0, t1, active_units, queued)
    slots: int
    dims: Any                  # the family's dims(conf)
    peak: dict

    @classmethod
    def build(cls, srv, w, dims: Any, peak: dict) -> "Context":
        events = devtrace.load(w.trace_dir)
        lo, hi = devtrace.window(events)
        return cls(
            events=devtrace.clip(events, lo, hi), lo_ns=lo, hi_ns=hi,
            t_lo=w.trace_lo, t_hi=w.trace_hi,
            spans=[s for s in srv.spans
                   if s[1] >= w.trace_lo and s[2] <= w.trace_hi],
            reqs=w.reqs, ticks=w.ticks, slots=srv.slots,
            dims=dims, peak=peak)

    # -- shared helpers ------------------------------------------------------
    @property
    def window_s(self) -> float:
        return (self.hi_ns - self.lo_ns) / 1e9

    @property
    def busy_s(self) -> float:
        return devtrace.busy_ns(self.events, self.lo_ns, self.hi_ns) / 1e9

    def calls(self, name: str) -> list:
        return [s for s in self.spans if s[0] == name]

    def module_s(self, fn_name: str) -> Optional[float]:
        """Device time of the jitted program of ``fn_name`` (its XLA
        module is named ``jit_<fn_name>``)."""
        mods = devtrace.modules(self.events, f"jit_{fn_name}")
        return sum(e[4] for e in mods) / 1e9 if mods else None

    def kernel_s(self, kernel: str) -> Optional[float]:
        """Device time of a Pallas kernel, named as its ``pallas_call``."""
        t = devtrace.op_ns(self.events, kernel)
        return t / 1e9 if t else None

    def breakdown(self) -> dict:
        return {"device_ops": devtrace.top_ops(self.events),
                "idle_gaps": devtrace.idle_gaps(self.events, self.lo_ns,
                                                self.hi_ns)}


def reader(name: str):
    """``metrics/<name>.py``, or for a quantity split by the end-to-end
    metric it moves (``decode_step_ms.chat``), ``metrics/<quantity>.py``."""
    path = HERE / "metrics" / f"{name}.py"
    if not path.exists():
        path = HERE / "metrics" / f"{name.split('.')[0]}.py"
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_all(metrics: List[dict], ctx: Context) -> Dict[str, Optional[float]]:
    return {m["name"]: reader(m["name"])(ctx) for m in metrics}
