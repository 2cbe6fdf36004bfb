"""One run of one cell: set-up, the open-loop window, the output check.

The served path is the program's own: ``ClusterRuntime.submit`` and
``ClusterRuntime.tick(dt_s=<wall seconds since the last tick>)`` over
``LMServingWorkload`` -> ``ContinuousBatcher`` -> ``ServingEngine``. The
harness adds host spans around ``tick``, ``prefill_fn``, ``_insert_jit``
and ``decode_fn`` (each span waits for its call's outputs, which the
batcher waits for right after anyway), polls every request's
``generated`` list after each tick, and stamps each new token with the
time the tick returned: the client sees tokens when a tick returns.

Times are ``time.perf_counter`` seconds from the first arrival of the
lead-in. The window is ``[lead_in_s, lead_in_s + seconds)``.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import json
import math
import os
import shutil
import sys
import tempfile
import time
from collections import deque
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
SPAN = "bench."

import traffic as traffic_mod  # noqa: E402
import work  # noqa: E402


# ---------------------------------------------------------------------------
# The cell's files.
# ---------------------------------------------------------------------------
def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, bench_path: Path = ROOT / "BENCHMARK.json"
              ) -> dict:
    bench = load_json(bench_path)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return {
        "cell": cell,
        "config": load_json(ROOT / conf["file"]),
        "mix": load_json(HERE / "traffic" / f"{cell['traffic']}.json"),
        "end_to_end": [m for m in bench["end_to_end"]
                       if name in m.get("workloads", [name])],
        "per_layer": [m for m in bench["per_layer"]
                      if name in m.get("workloads", [name])],
    }


def seed32(seed: int) -> int:
    """A 32-bit key for JAX from any non-negative seed."""
    return int(np.random.SeedSequence(seed).generate_state(1)[0])


# ---------------------------------------------------------------------------
# The server under test.
# ---------------------------------------------------------------------------
def family(conf: dict):
    """``families/<family>.py``, the module the configuration names: all
    that the harness knows of the model's shape."""
    return importlib.import_module(f"families.{conf['family']}")


class Server:
    """Engine, batcher and runtime for one cell, with the harness's spans."""

    def __init__(self, cfg, mix: dict, seed: int, serve: dict):
        import jax

        from repro.config import ServeConfig
        from repro.core.cluster import ClusterSpec, tpu_v5e_pod
        from repro.models import model as lm
        from repro.runtime import (ClusterRuntime, LMServingWorkload,
                                   ScalePolicy)
        from repro.serving.engine import ServingEngine

        self.jax = jax
        self.slots = mix["slots"]
        self.engine = ServingEngine(cfg, ServeConfig(
            max_seq_len=mix["max_seq_len"], **serve))
        init = jax.jit(lambda key: lm.init_params(cfg, key))
        self.engine.load(init(jax.random.key(seed32(seed))))
        self.workload = LMServingWorkload(self.engine, slots=self.slots)
        self.batcher = self.workload.batcher
        spec = ClusterSpec(name=f"{self.slots}-slot", unit=tpu_v5e_pod(1).unit,
                           n_units=self.slots, p_shared=0.0)
        self.runtime = ClusterRuntime(
            spec, self.workload, policy=ScalePolicy(),
            unit_rate=mix["knee_rps"] / self.slots)
        self.spans: List[tuple] = []     # (name, t0, t1, info)
        self.fifo: deque = deque()       # submitted, not yet prefilled
        self.tracking = False
        self.origin = time.perf_counter()
        self._instrument()

    def now(self) -> float:
        return time.perf_counter() - self.origin

    def _span(self, name: str, fn: Callable, info: Callable) -> Callable:
        jax = self.jax

        def wrapped(*a, **k):
            t0 = self.now()
            with jax.profiler.TraceAnnotation(SPAN + name):
                out = fn(*a, **k)
                jax.block_until_ready(out)
            if self.tracking:
                self.spans.append((name, t0, self.now(), info(a, t0)))
            return out
        return wrapped

    def _instrument(self) -> None:
        eng, bat = self.engine, self.batcher

        def prefill_info(a, t0):
            # the batcher admits in FIFO order, one prefill a request
            self.fifo.popleft().prefill_s = t0
            return int(a[1]["tokens"].shape[1])

        def decode_info(a, t0):
            return [int(bat.positions[s]) + 1 for s in range(bat.slots)
                    if bat.active[s] is not None]

        eng.prefill_fn = self._span("prefill", eng.prefill_fn, prefill_info)
        eng.decode_fn = self._span("decode", eng.decode_fn, decode_info)
        bat._insert_jit = self._span("insert", bat._insert_jit,
                                     lambda a, t0: None)

    def tick(self, dt: float):
        t0 = self.now()
        with self.jax.profiler.TraceAnnotation(SPAN + "tick"):
            stats = self.runtime.tick(dt_s=dt)
        return t0, self.now(), stats

    def warm(self, lengths: List[int]) -> None:
        """Compile and run every shape the window uses: a prefill at each
        grid length, an insert into every slot, and the decode step."""
        bat = self.batcher
        queue = [lengths[i % len(lengths)]
                 for i in range(max(self.slots, len(lengths)))]
        while queue:
            for n in queue[: self.slots]:
                bat.submit(np.zeros(n, np.int32), max_new_tokens=2)
            queue = queue[self.slots:]
            bat.run_to_completion()
        bat.finished.clear()

    def close(self) -> None:
        """Drop the program's device state."""
        self.engine.params = None
        self.batcher.caches = None
        self.runtime = self.workload = self.batcher = self.engine = None
        gc.collect()


# ---------------------------------------------------------------------------
# The window.
# ---------------------------------------------------------------------------
class CompileCounter:
    """Counts compilations and persistent-cache loads while ``on``."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax

        self.on = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, duration: float, **_):
        if self.on and event in self.EVENTS:
            self.count += 1


def pct(values: List[float], q: float) -> Optional[float]:
    return float(np.percentile(values, q)) if values else None


@dataclasses.dataclass
class Window:
    reqs: list
    lo: float
    hi: float
    ticks: List[tuple]             # (t0, t1, active_units, queued)
    lateness: List[float]
    trace_lo: Optional[float] = None
    trace_hi: Optional[float] = None
    trace_dir: Optional[str] = None


def drive(srv: Server, mix: dict, reqs: list, seconds: float,
          trace_s: float = 0.0, counter: Optional[CompileCounter] = None,
          drain_s: float = 60.0) -> Window:
    """Offer ``reqs`` to the runtime, open loop, each when it is due,
    through the lead-in and the window; then keep the arrivals coming
    until every request due in the window has its first token (at most
    ``drain_s`` more)."""
    jax = srv.jax
    lo = float(mix["lead_in_s"])
    hi = lo + seconds
    w = Window(reqs=reqs, lo=lo, hi=hi, ticks=[], lateness=[])
    live: List = []
    i = 0
    srv.tracking = True
    srv.origin = time.perf_counter()
    last = srv.now()
    tracer = None
    while True:
        now = srv.now()
        if counter is not None:
            counter.on = lo <= now < hi
        if trace_s and w.trace_lo is None and now >= lo:
            w.trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(w.trace_dir)
            tracer = jax.profiler.TraceAnnotation(SPAN + "window")
            tracer.__enter__()
            w.trace_lo = now = srv.now()
        if tracer is not None and now >= w.trace_lo + trace_s:
            w.trace_hi = now
            tracer.__exit__(None, None, None)
            tracer = None
            jax.profiler.stop_trace()
            now = srv.now()
        # arrivals
        while i < len(reqs) and reqs[i].due_s <= now:
            r = reqs[i]
            r.submit_s = srv.now()
            w.lateness.append(r.submit_s - r.due_s)
            srv.runtime.submit(r.prompt, max_new_tokens=r.max_new)
            r.handle = srv.batcher.queue[-1]
            srv.fifo.append(r)
            live.append(r)
            i += 1
        if now >= hi and (now >= hi + drain_s or all(
                r.token_s for r in reqs[:i] if r.due_s < hi)):
            break
        if not live and i < len(reqs):
            time.sleep(max(0.0, min(reqs[i].due_s, hi) - now))
            continue
        if not live and i >= len(reqs):
            break
        t0, t1, stats = srv.tick(srv.now() - last)
        last = t0
        w.ticks.append((t0, t1, stats.active_units, stats.queued))
        for r in live:
            while len(r.token_s) < len(r.handle.generated):
                r.token_s.append(t1)
        live = [r for r in live if not r.handle.done]
    if counter is not None:
        counter.on = False
    if tracer is not None:
        w.trace_hi = srv.now()
        tracer.__exit__(None, None, None)
        jax.profiler.stop_trace()
    srv.tracking = False
    return w


def end_to_end(w: Window, drain_s: float = 60.0) -> Dict[str, float]:
    """Time to first token of every request due in the window (a request
    with none counts at the end of the wait), every gap between tokens
    whose later token came in the window, and the tokens that came in
    the window."""
    due = [r for r in w.reqs if w.lo <= r.due_s < w.hi]
    ttft = [(r.token_s[0] if r.token_s else w.hi + drain_s) - r.due_s
            for r in due]
    gaps = [b - a for r in w.reqs for a, b in zip(r.token_s, r.token_s[1:])
            if w.lo <= b < w.hi]
    toks = sum(1 for r in w.reqs for t in r.token_s if w.lo <= t < w.hi)
    return {
        "ttft_p90_ms": pct(ttft, 90) * 1e3 if ttft else None,
        "itl_p95_ms": pct(gaps, 95) * 1e3 if gaps else None,
        "output_tokens_per_s": toks / (w.hi - w.lo),
        "attempted": len(due),
        "failed": sum(1 for r in due if not r.token_s),
        "gaps": len(gaps),
        "tokens": toks,
    }


# ---------------------------------------------------------------------------
# The output check.
# ---------------------------------------------------------------------------
def sample_for_check(reqs: list, check: dict, seed: int) -> list:
    """Finished requests drawn from the seed, the longest first, until
    ``check['tokens']`` served tokens or ``check['requests']`` requests."""
    done = [r for r in reqs if r.handle is not None and r.handle.done]
    if not done:
        return []
    done.sort(key=lambda r: (-len(r.handle.generated), len(r.prompt)))
    rng = np.random.default_rng([seed, 1])
    rest = [done[j] for j in rng.permutation(len(done) - 1) + 1]
    out, n = [done[0]], len(done[0].handle.generated)
    for r in rest:
        if n >= check["tokens"] or len(out) >= check["requests"]:
            break
        out.append(r)
        n += len(r.handle.generated)
    return out


def check_outputs(conf: dict, mix: dict, seed: int, sample: list) -> dict:
    """Gaps of the served tokens below the reference's best logit."""
    gaps = family(conf).gaps(
        conf, seed32(seed), [r.prompt for r in sample],
        [list(r.handle.generated) for r in sample],
        traffic_mod.max_output(mix))
    flat = np.concatenate(gaps) if gaps else np.zeros(0)
    return {
        "requests_compared": len(sample),
        "max_logit_gap": float(flat.max()) if flat.size else math.inf,
        "mean_logit_gap": float(flat.mean()) if flat.size else math.inf,
        "tokens_compared": int(flat.size)}


# ---------------------------------------------------------------------------
# A whole run.
# ---------------------------------------------------------------------------
def device_info(jax) -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_bytes(jax) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def run(c: dict, seed: int, seconds: float, trace: bool, t_start: float,
        serve: Optional[dict] = None, patch: Optional[Callable] = None,
        peak_kind: Optional[str] = None) -> dict:
    """Run the cell ``c`` (from :func:`load_cell`) once. ``patch(server)``
    lets a test break the timed path; ``serve`` overrides ServeConfig
    fields (the program's int8 weights, the control). Returns the result
    line."""
    import jax

    from repro.config import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    conf, mix = c["config"], c["mix"]
    fam = family(conf)
    cfg = fam.program_config(conf)
    dev = device_info(jax)
    peak = work.peaks(peak_kind or dev["kind"])
    counter = CompileCounter()

    t = time.time()
    srv = Server(cfg, mix, seed, {**conf.get("serve", {}), **(serve or {})})
    log(f"set-up: weights {time.time() - t:.2f} s")
    t = time.time()
    srv.warm(mix["prompt"]["grid"])
    log(f"set-up: warm-up {time.time() - t:.2f} s")
    if patch is not None:
        patch(srv)
    lead = float(mix["lead_in_s"])
    horizon = lead + seconds + 60.0
    reqs = traffic_mod.make_requests(
        mix, seed, fam.vocab(conf),
        traffic_mod.request_count(mix, horizon))
    setup_s = time.time() - t_start + lead
    w = drive(srv, mix, reqs, seconds, trace_s=mix["trace_s"] if trace
              else 0.0, counter=counter)
    e2e = end_to_end(w)
    dev["memory_peak_bytes"] = peak_bytes(jax)

    per_layer, breakdown = {}, None
    if trace:
        import readers
        ctx = readers.Context.build(srv, w, fam.dims(conf), peak)
        per_layer = readers.read_all(c["per_layer"], ctx)
        dev["busy_s"] = ctx.busy_s
        dev["window_s"] = ctx.window_s
        breakdown = ctx.breakdown()
        shutil.rmtree(w.trace_dir, ignore_errors=True)

    sample = sample_for_check(reqs, mix["check"], seed)
    srv.close()
    del srv
    t = time.time()
    chk = check_outputs(conf, mix, seed, sample)
    log(f"check: reference {time.time() - t:.2f} s")

    sent = sum(1 for r in reqs if r.submit_s is not None)
    log(f"device: {json.dumps(dev)}")
    log(f"requests: sent {sent}, due in window {e2e['attempted']}, "
        f"succeeded {e2e['attempted'] - e2e['failed']}, "
        f"failed {e2e['failed']}")
    log(f"generator late p95: {pct(w.lateness, 95)} s")
    log(f"compiles in window: {counter.count}")
    log(f"peak_bytes_in_use: {dev['memory_peak_bytes']}")
    log(f"window: {e2e['tokens']} tokens, {e2e['gaps']} gaps, "
        f"{len(w.ticks)} ticks")

    limit = mix["check"]["mean_logit_gap"]
    checks = {
        "mean_logit_gap": {"value": chk["mean_logit_gap"], "limit": limit},
        "tokens_compared": {"value": chk["tokens_compared"],
                            "limit": mix["check"]["min_tokens"]},
    }
    correct = (chk["mean_logit_gap"] <= limit
               and chk["tokens_compared"] >= mix["check"]["min_tokens"])
    log(f"check max_logit_gap {chk['max_logit_gap']} (not compared)")
    for k, v in checks.items():
        log(f"check {k} {v['value']} limit {v['limit']}")

    units = {m["name"]: m["unit"] for m in c["end_to_end"] + c["per_layer"]}
    values = dict(e2e, setup_s=setup_s)
    if trace:
        chosen = {k: v for k, v in per_layer.items() if v is not None}
    else:
        chosen = {m["name"]: values[m["name"]] for m in c["end_to_end"]
                  if values.get(m["name"]) is not None}
    out = {
        "correct": bool(correct),
        "attempted": e2e["attempted"],
        "failed": e2e["failed"],
        "metrics": {k: {"value": v, "unit": units[k]}
                    for k, v in chosen.items()},
        "device": dev,
    }
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["readings"] = chk
    out["check"] = checks
    return out


def cache_env() -> None:
    """The persistent compile cache lives in the checkout, at a fixed
    path, whatever the environment says."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
