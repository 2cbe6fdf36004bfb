"""Reduction of a profiler trace to the numbers the per-layer metrics use.

``load`` flattens the ``.xplane.pb`` that ``jax.profiler`` writes into a
list of events ``[plane, line, name, start_ns, dur_ns]``. Everything
else here works on that list, so a recorded list (``tests/data``) pins
the arithmetic.

* Device operations are the events of the ``XLA Ops`` line of each
  ``/device:`` plane, named by their HLO instruction (``%fusion.12``;
  a Pallas kernel by its own name, ``%decode_attention.3``); executables
  are the events of its ``XLA Modules`` line (``jit__decode(...)``).
  Control-flow operations (``while``, ``conditional``, ``call``) span
  the operations they run and are left out.
* Busy time is the union of the device operations' intervals inside the
  traced window, averaged over the devices; idle share is 1 minus busy
  over the window.
* The traced window and the host spans are the harness's
  ``TraceAnnotation`` events on the host plane, on the same clock.
* An idle gap is named by the innermost host span open at its middle.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

Event = list   # [plane, line, name, start_ns, dur_ns]

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
CONTAINERS = ("while", "conditional", "call")
_BASE = re.compile(r"^%?([A-Za-z_][\w\-]*?)(\.\d+)*(\.remat\d*)?$")


def base_name(op: str) -> str:
    """``%copy.99.remat2`` -> ``copy``; ``%decode_attention.3`` ->
    ``decode_attention``."""
    m = _BASE.match(op)
    return m.group(1) if m else op


def load(trace_dir: str) -> List[Event]:
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    out: List[Event] = []
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                name = ev.name
                if device and line.name == OPS_LINE:
                    name = name.split(" = ")[0]
                    if base_name(name) in CONTAINERS:
                        continue
                elif not device and not name.startswith(SPAN_PREFIX):
                    continue
                out.append([plane.name, line.name, name,
                            int(ev.start_ns), int(ev.duration_ns)])
    return out


def window(events: Sequence[Event]) -> Tuple[int, int]:
    spans = [e for e in events if e[2] == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
    e = spans[0]
    return e[3], e[3] + e[4]


def clip(events: Sequence[Event], lo: int, hi: int) -> List[Event]:
    """The events that lie wholly inside ``[lo, hi]``."""
    return [e for e in events if e[3] >= lo and e[3] + e[4] <= hi]


def device_ops(events: Sequence[Event]) -> Dict[str, List[Event]]:
    by_dev: Dict[str, List[Event]] = defaultdict(list)
    for e in events:
        if e[0].startswith("/device:") and e[1] == OPS_LINE:
            by_dev[e[0]].append(e)
    return by_dev


def modules(events: Sequence[Event], prefix: str) -> List[Event]:
    return [e for e in events if e[0].startswith("/device:")
            and e[1] == MODULES_LINE and e[2].startswith(prefix)]


def merged(intervals: Sequence[Tuple[int, int]], lo: int, hi: int
           ) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def busy_ns(events: Sequence[Event], lo: int, hi: int) -> float:
    """Union of device-operation time in ``[lo, hi]``, mean over devices."""
    devs = device_ops(events)
    if not devs:
        return 0.0
    total = sum(sum(b - a for a, b in merged(
        [(e[3], e[3] + e[4]) for e in evs], lo, hi))
        for evs in devs.values())
    return total / len(devs)


def idle_share(events: Sequence[Event], lo: int, hi: int) -> Optional[float]:
    if not device_ops(events) or hi <= lo:
        return None
    return 1.0 - busy_ns(events, lo, hi) / (hi - lo)


def op_ns(events: Sequence[Event], base: str) -> float:
    """Device time of the operations named ``base`` (any instance)."""
    return float(sum(e[4] for evs in device_ops(events).values()
                     for e in evs if base_name(e[2]) == base))


def top_ops(events: Sequence[Event], n: int = 10) -> List[List]:
    """The ``n`` operation kinds that took most device time, in seconds
    (the sum over devices)."""
    acc: Dict[str, int] = defaultdict(int)
    for evs in device_ops(events).values():
        for e in evs:
            acc[base_name(e[2])] += e[4]
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in top]


def idle_gaps(events: Sequence[Event], lo: int, hi: int, n: int = 10
              ) -> List[List]:
    """The ``n`` longest idle gaps of the first device, each named by the
    innermost host span open at its middle (``client`` where none is:
    the harness between ticks)."""
    devs = device_ops(events)
    if not devs:
        return []
    first = sorted(devs)[0]
    busy = merged([(e[3], e[3] + e[4]) for e in devs[first]], lo, hi)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    spans = [e for e in events if not e[0].startswith("/device:")
             and e[2] != WINDOW_SPAN]
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (a + b) // 2
        open_ = [s for s in spans if s[3] <= mid < s[3] + s[4]]
        name = min(open_, key=lambda s: s[4])[2] if open_ else "client"
        out.append([name, (b - a) / 1e9])
    return out
