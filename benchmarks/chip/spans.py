"""The program's own spans and scopes in a run of one cell.

    python3 benchmarks/chip/spans.py --workload internlm2-1.8b.chat \
        --seed 7 --seconds 50 --trace 1 [--fixture PATH]

Runs the cell as ``run.py`` does, with the program's span recorder
(``repro.obs.serving``) turned on at the end of warm-up. With ``--trace
1`` the trace is read by :func:`load`, which keeps what ``devtrace.load``
keeps and adds, as a sixth element of each event, the program's
``repro.*`` host spans with their arguments and each device operation's
scope path (its HLO ``op_name``). The result line then holds the metrics
of ``SPAN_METRICS`` beside the cell's own; standard error gets the idle
time split by the layer whose span was innermost (the client's part
where none was), the decode step's device time by model scope, the
longest idle gaps with the compiles and garbage collections that
overlap them, and a summary of the ``repro.gate`` counters. ``--fixture``
writes a cut of the trace for the tests. With ``--trace 0`` the run
measures what the recorder costs the end-to-end metrics.

Against a program without the recorder the span metrics read ``None``.
"""
import time

T_START = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402
from collections import defaultdict  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import (Dict, Iterator, List, Optional, Sequence,  # noqa: E402
                    Tuple)

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

import devtrace  # noqa: E402

PREFIX = "repro."
#: (metric, unit) read from the program's spans and scopes
SPAN_METRICS = [("queued_p90_ms", "ms"), ("admit_share", "%"),
                ("idle_runtime_share", "%"), ("idle_batcher_share", "%"),
                ("idle_engine_share", "%"), ("decode_attn_core_ms", "ms")]
LAYERS = ("runtime", "batcher", "engine")
#: the model's named scopes (``jax.named_scope`` in ``models/``)
SCOPES = ("attn_core", "kv_write", "attn", "mlp", "moe", "norm", "embed",
          "lm_head")
DECODE_MODULE = "jit__decode"
LOADED: List[list] = []       # what the last :func:`load` returned

_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) \(")
_INSTRUCTION = re.compile(r"^\s*(ROOT )?%([\w.\-]+) = ")
_OP_NAME = re.compile(r'metadata=\{[^}]*op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%([\w.\-]+)")


# ---------------------------------------------------------------------------
# The trace.
# ---------------------------------------------------------------------------
def op_names(hlo_text: str) -> Dict[str, str]:
    """Each instruction of a compiled module's HLO text mapped to its
    ``op_name`` (``jit(_decode)/while/body/.../attn/attn_core/...``). An
    instruction without one, such as most fusions, takes the ``op_name``
    of the root of the computation it calls."""
    own: Dict[str, str] = {}
    calls: Dict[str, str] = {}
    root: Dict[str, str] = {}
    comp = ""
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name = m.group(2)
        if m.group(1):
            root[comp] = name
        op = _OP_NAME.search(line)
        if op:
            own[name] = op.group(1)
        c = _CALLS.search(line)
        if c:
            calls[name] = c.group(1)
    out: Dict[str, str] = {}
    for name in set(own) | set(calls):
        seen, n = set(), name
        while n not in own and n in calls and n not in seen:
            seen.add(n)
            n = root.get(calls[n], "")
        out[name] = own.get(n, "")
    return out


def load(trace_dir: str, scopes: Optional[Dict[str, str]] = None
         ) -> List[list]:
    """``devtrace.load``'s events, each with a sixth element: for a
    ``repro.*`` host span (which ``devtrace.load`` leaves out) its
    arguments; for a device operation inside the decode program its
    ``op_name`` from ``scopes`` (:func:`op_names` of that program), the
    profiler's events carrying none; ``None`` for the rest."""
    import glob
    import os

    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    out: List[list] = []
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name not in (devtrace.OPS_LINE,
                                            devtrace.MODULES_LINE):
                continue
            for ev in line.events:
                name, extra = ev.name, None
                if device and line.name == devtrace.OPS_LINE:
                    name = name.split(" = ")[0]
                    if devtrace.base_name(name) in devtrace.CONTAINERS:
                        continue
                elif not device and name.startswith(PREFIX):
                    extra = dict(ev.stats)
                elif not device and not name.startswith(devtrace.SPAN_PREFIX):
                    continue
                out.append([plane.name, line.name, name, int(ev.start_ns),
                            int(ev.duration_ns), extra])
    if scopes:
        for op in in_decode(out):
            op[5] = scopes.get(op[2].lstrip("%"), "")
    LOADED[:] = [out]
    return out


def in_decode(events: Sequence[list]) -> Iterator[list]:
    """Each device operation that runs inside a call of the decode
    program."""
    mods = sorted((e[3], e[3] + e[4]) for e in events
                  if e[0].startswith("/device:")
                  and e[1] == devtrace.MODULES_LINE
                  and e[2].startswith(DECODE_MODULE))
    ops = sorted((e for evs in devtrace.device_ops(events).values()
                  for e in evs), key=lambda e: e[3])
    j = 0
    for a, b in mods:
        while j < len(ops) and ops[j][3] < a:
            j += 1
        while j < len(ops) and ops[j][3] + ops[j][4] <= b:
            yield ops[j]
            j += 1


# ---------------------------------------------------------------------------
# Reductions (pinned by tests/test_spans.py).
# ---------------------------------------------------------------------------
def host_spans(events: Sequence[list]) -> List[Tuple[str, int, int, dict]]:
    """The program's spans: ``(name, t0_ns, t1_ns, args)``."""
    return [(e[2], e[3], e[3] + e[4], e[5] or {}) for e in events
            if not e[0].startswith("/device:") and e[2].startswith(PREFIX)
            and len(e) > 5]


def innermost(spans: Sequence[Tuple[str, int, int, dict]], lo: int, hi: int
              ) -> List[Tuple[int, int, Optional[str]]]:
    """``[lo, hi]`` cut into segments, each labelled with the innermost
    span open over it (``None`` where none is). The spans come from one
    host thread, so they nest."""
    out: List[Tuple[int, int, Optional[str]]] = []
    stack: List[Tuple[int, str]] = []     # (end, name), innermost last
    t = lo

    def advance(x: int) -> None:
        nonlocal t
        while stack and stack[-1][0] <= x:
            end, name = stack.pop()
            if end > t:
                out.append((t, end, name))
                t = end
        if x > t:
            out.append((t, x, stack[-1][1] if stack else None))
            t = x

    for name, a, b, _ in sorted(spans, key=lambda s: (s[1], -s[2])):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        advance(a)
        stack.append((b, name))
    advance(hi)
    return out


def idle_intervals(events: Sequence[list], lo: int, hi: int
                   ) -> List[Tuple[int, int]]:
    """Where the first device ran no operation inside ``[lo, hi]``."""
    devs = devtrace.device_ops(events)
    if not devs:
        return []
    busy = devtrace.merged([(e[3], e[3] + e[4]) for e in devs[sorted(devs)[0]]],
                           lo, hi)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    return [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]


def layer_of(name: Optional[str]) -> str:
    """``repro.batcher.admit`` -> ``batcher``; no span -> ``client``."""
    return name.split(".")[1] if name else "client"


def idle_by_layer(events: Sequence[list], lo: int, hi: int
                  ) -> Optional[Dict[str, int]]:
    """Idle nanoseconds of the first device by the layer of the innermost
    program span open over them; ``None`` without program spans."""
    spans = host_spans(events)
    if not spans or not devtrace.device_ops(events):
        return None
    out: Dict[str, int] = defaultdict(int)
    segs = innermost(spans, lo, hi)
    j = 0
    for a, b in idle_intervals(events, lo, hi):
        while j < len(segs) and segs[j][1] <= a:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < b:
            s0, s1, name = segs[k]
            out[layer_of(name)] += min(b, s1) - max(a, s0)
            k += 1
    return dict(out)


def scope_label(path: str) -> str:
    """The innermost model scope on an operation's path, ``unscoped``
    where it has none."""
    parts = path.split("/")
    for part in reversed(parts):
        if part in SCOPES:
            return part
    return "unscoped"


def decode_scopes(events: Sequence[list]
                  ) -> Tuple[int, Optional[Dict[str, int]]]:
    """Decode calls, and the device nanoseconds of the operations inside
    them by scope (``None`` where no operation carries a scope path)."""
    calls = sum(1 for e in events if e[0].startswith("/device:")
                and e[1] == devtrace.MODULES_LINE
                and e[2].startswith(DECODE_MODULE))
    out: Dict[str, int] = defaultdict(int)
    scoped = False
    for op in in_decode(events):
        path = op[5] if len(op) > 5 else None
        scoped = scoped or bool(path)
        out[scope_label(path or "")] += op[4]
    return calls, dict(out) if scoped else None


def union_ns(intervals: Sequence[Tuple[int, int]], lo: int, hi: int) -> int:
    return sum(b - a for a, b in devtrace.merged(intervals, lo, hi))


# ---------------------------------------------------------------------------
# The run.
# ---------------------------------------------------------------------------
class GcClock:
    """Garbage collections, timed on ``time.perf_counter_ns``."""

    def __init__(self):
        self.pauses: List[Tuple[int, int, int]] = []   # (t0, t1, generation)
        self._t0 = 0
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter_ns()
        else:
            self.pauses.append((self._t0, time.perf_counter_ns(),
                                info["generation"]))


def clock_offset(events: Sequence[list], rec) -> Optional[int]:
    """Profiler clock minus ``perf_counter_ns``, from the tick spans that
    both hold."""
    mem = {s.args.get("tick"): s.t0_ns for s in rec.named("repro.runtime.tick")}
    diffs = sorted(t0 - mem[a["tick"]] for n, t0, _, a in host_spans(events)
                   if n == "repro.runtime.tick" and a.get("tick") in mem)
    return diffs[len(diffs) // 2] if diffs else None


def gate_summary(rec, slots: int, lo: int = 0, hi: int = 2**63) -> str:
    """The ``repro.gate`` counters stamped in ``[lo, hi]``
    (``perf_counter_ns``): ticks in which fewer units than slots were
    active while requests queued, and the lowest counts."""
    gate = [c.values for c in rec.counters
            if c.name.startswith("repro.gate") and lo <= c.t_ns <= hi]
    if not gate:
        return "no ticks"
    starved = sum(1 for v in gate if v["active"] < slots and v["queued"])
    return (f"{len(gate)} ticks, {starved} with active < {slots} while "
            f"requests queued; lowest active "
            f"{min(v['active'] for v in gate)}, lowest granted "
            f"{min(v['granted'] for v in gate)}, most queued "
            f"{max(v['queued'] for v in gate)}")


def report(events: Sequence[list], lo: int, hi: int, rec, gcs: GcClock,
           slots: int, log) -> None:
    """The stderr summary of a traced run (``events`` inside the window
    ``[lo, hi]`` on the profiler's clock)."""
    win = hi - lo
    idle = devtrace.idle_share(events, lo, hi)
    split = idle_by_layer(events, lo, hi) or {}
    log("idle by layer (% of window): " + ", ".join(
        f"{k} {100.0 * split.get(k, 0) / win:.4f}"
        for k in LAYERS + ("client",))
        + f"; idle_share {100.0 * (idle or 0.0):.4f}")
    calls, scopes = decode_scopes(events)
    if scopes and calls:
        log(f"decode step by scope (ms per call, {calls} calls): " + ", ".join(
            f"{k} {v / calls / 1e6:.3f}" for k, v in
            sorted(scopes.items(), key=lambda kv: -kv[1]))
            + f"; all {sum(scopes.values()) / calls / 1e6:.3f}")
    off = clock_offset(events, rec)
    if off is None:
        return
    comp = [(s.t0_ns + off, s.t1_ns + off, s.args["event"])
            for s in rec.named("repro.compile")]
    pauses = [(a + off, b + off, f"gen{g} {(b - a) / 1e6:.1f} ms")
              for a, b, g in gcs.pauses]
    segs = innermost(host_spans(events), lo, hi)
    gaps = sorted(idle_intervals(events, lo, hi), key=lambda g: g[0] - g[1])
    for a, b in gaps[:10]:
        mid = (a + b) // 2
        label = next((n for s0, s1, n in segs if s0 <= mid < s1), None)
        c = [x[2] for x in comp if x[0] < b and x[1] > a]
        g = [x[2] for x in pauses if x[0] < b and x[1] > a]
        log(f"idle gap {(b - a) / 1e6:.3f} ms at +{(a - lo) / 1e6:.1f} ms in "
            f"{label or 'client'}; compiles {c or 'none'}; gc {g or 'none'}")
    c = [x for x in comp if x[0] < hi and x[1] > lo]
    g = [(x[1] - x[0]) / 1e6 for x in pauses if x[0] < hi and x[1] > lo]
    log(f"in window: {len(c)} compiles or cache loads, {len(g)} collections "
        f"(longest {max(g, default=0.0):.1f} ms, {sum(g):.1f} ms in all); "
        f"gate {gate_summary(rec, slots, lo - off, hi - off)}")


def cut(events: Sequence[list], lo: int, hi: int) -> dict:
    """The events wholly inside ``[lo, hi]``, with a window span over it."""
    host = next(e[0] for e in events if not e[0].startswith("/device:"))
    out = devtrace.clip(events, lo, hi)
    out.append([host, "python3", devtrace.WINDOW_SPAN, lo, hi - lo, None])
    return {"about": "A cut of a --trace 1 run of spans.py on one TPU v5e: "
                     "events as spans.load returns them.",
            "events": out}


def fixture_window(events: Sequence[list], lo: int, hi: int
                   ) -> Tuple[int, int]:
    """Three ticks, from the one that admits the first request in the
    window (or the first tick)."""
    spans = host_spans(devtrace.clip(events, lo, hi))
    ticks = [s for s in spans if s[0] == "repro.runtime.tick"]
    admit = next((s for s in spans if s[0] == "repro.batcher.admit"), None)
    first = 0 if admit is None else max(
        i for i, s in enumerate(ticks) if s[1] <= admit[1])
    three = ticks[first:first + 3]
    return three[0][1] - 1000, three[-1][2] + 1000


def decode_op_names(srv) -> Dict[str, str]:
    """:func:`op_names` of the decode program the server runs: the same
    program, compiled again (a load from the persistent cache)."""
    import jax.numpy as jnp

    from repro.serving.engine import ServingEngine

    eng, bat = srv.engine, srv.batcher
    zeros = jnp.zeros((bat.slots,), jnp.int32)
    compiled = ServingEngine(eng.cfg, eng.scfg).decode_fn.lower(
        eng.params, zeros[:, None], bat.caches, zeros).compile()
    return op_names(compiled.as_text())


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--fixture", help="write a cut of the trace here")
    args = ap.parse_args()

    import harness

    cell = harness.load_cell(args.workload)
    split = cell["cell"]["traffic"]
    cell["per_layer"] = cell["per_layer"] + [
        {"name": f"{n}.{split}", "unit": u} for n, u in SPAN_METRICS]
    slots = cell["mix"]["slots"]
    harness.cache_env()
    import jax

    import repro  # noqa: F401  the system under test, from ../../src

    devs = jax.devices()
    chips = cell["cell"]["chips"]
    if devs[0].platform != "tpu" or len(devs) < chips:
        harness.log(f"needs {chips} TPU chip(s); JAX found {len(devs)} "
                    f"{devs[0].platform} device(s)")
        return 3
    try:
        from repro.obs import serving
    except ImportError:
        harness.log("the program has no span recorder")
        serving = None
    scopes: Dict[str, str] = {}
    devtrace.load = lambda trace_dir: load(trace_dir, scopes)
    gcs = GcClock()

    def record(srv):
        if serving is not None:
            if args.trace:
                scopes.update(decode_op_names(srv))
            serving.start()

    out = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                      T_START, patch=record)
    rec = serving.stop() if serving is not None else None
    if rec is not None:
        harness.log(f"gate, whole run: {gate_summary(rec, slots)}")
    if args.trace and LOADED and rec is not None:
        events = LOADED[0]
        lo, hi = devtrace.window(events)
        report(devtrace.clip(events, lo, hi), lo, hi, rec, gcs, slots,
               harness.log)
        if args.fixture:
            a, b = fixture_window(events, lo, hi)
            with open(args.fixture, "w") as f:
                json.dump(cut(events, a, b), f)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
