"""Serving-path spans and counters: the serving counterpart of the probes.

The served path (``MultiTenantRuntime._tick_all`` -> ``ContinuousBatcher``
-> ``ServingEngine``) calls this module at its layer boundaries. It is
off by default, and a call site then costs one ``is None`` check: it
enters no ``TraceAnnotation``, allocates nothing and reads no clock.

    rec = serving.RECORDER
    with serving.OFF if rec is None else rec.span("repro.batcher.sample"):
        nxt = np.asarray(jnp.argmax(logits, axis=-1))

When on (:func:`start` or :func:`recording`), :meth:`SpanRecorder.span`
keeps ``(name, t0_ns, t1_ns, parent, args)`` in memory on
``time.perf_counter_ns`` and enters ``jax.profiler.TraceAnnotation(name,
**args)``, so that under the profiler the span also lands on the host
plane, on the device trace's own clock. :meth:`SpanRecorder.count`
keeps a timestamped counter. While on, a ``jax.monitoring`` listener
records each backend compile and persistent-cache load as a
``repro.compile`` span that ends when the event fires. No span adds a
host sync: each ends at one the program already makes, or at the end
of its Python work.

Spans (``args`` given at entry go to the profiler too; those added to
the yielded dict before exit stay in memory):

* ``repro.runtime.tick`` (``tick``), ``repro.runtime.gate`` (desired ->
  grant -> ``apply_target`` -> hedging), ``repro.runtime.account``
  (``pool.charge``, ``governor.note``, ``drain``);
* ``repro.batcher.step`` (``live``, ``positions``: the sum of the live
  slots' positions, ``syncs``: host readbacks in the step),
  ``repro.batcher.admit`` (``rid``, ``slot``, ``prompt_len``,
  ``queued_ns``: submit to admit), ``repro.batcher.sample``,
  ``repro.batcher.update`` (``finished``: the rids that completed);
* ``repro.engine.prefill``, ``repro.engine.decode``: the uploads and the
  dispatch of the jitted function (``state_bytes``, for a model with
  Mamba layers: the recurrent state the prefill makes for its slot, or
  that the decode step reads and writes back for the live slots, of
  ``state_bytes_all`` for every slot; on decode, for a model with
  attention layers, ``kv_blocks``: the KV blocks per
  layer the decode kernel fetches, each slot's up to the one holding its
  new position, of ``kv_blocks_all`` in the cache);
* ``repro.compile`` (``event``, ``duration_s``).

Counters: ``repro.gate`` once per tick per tenant (``rate``: the
offered-rate estimate, ``desired``, ``granted``, ``active``, ``hedged``,
``queued`` after the step); ``repro.compile`` (``compiles`` so far).

:meth:`SpanRecorder.save` writes Chrome trace JSON through
:class:`~repro.obs.trace.TraceRecorder`; :func:`request_latencies` reads
each request's time to first token and its gaps between tokens from the
spans.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Any, Dict, Iterator, List, Optional, Tuple

from repro.obs.trace import TraceRecorder

__all__ = ["RECORDER", "OFF", "Span", "Counter", "SpanRecorder", "start",
           "stop", "recording", "request_latencies"]

#: the recorder the served path writes to; ``None`` when off
RECORDER: Optional["SpanRecorder"] = None
#: what a call site enters when the recorder is off
OFF = contextlib.nullcontext()

COMPILE_EVENTS = {
    "/jax/core/compile/backend_compile_duration": "backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}


@dataclass
class Span:
    name: str
    t0_ns: int
    t1_ns: int                   # -1 while open
    parent: Optional[int]        # index of the enclosing span in ``spans``
    args: Dict[str, Any]


@dataclass
class Counter:
    name: str
    t_ns: int
    values: Dict[str, float]


@dataclass
class SpanRecorder:
    """Spans and counters of one recording, in memory."""

    spans: List[Span] = field(default_factory=list)
    counters: List[Counter] = field(default_factory=list)
    compiles: int = 0
    origin_ns: int = field(default_factory=time.perf_counter_ns)
    _open: List[int] = field(default_factory=list)

    @staticmethod
    def now_ns() -> int:
        return time.perf_counter_ns()

    @contextlib.contextmanager
    def span(self, name: str, **args: Any) -> Iterator[Dict[str, Any]]:
        """Record ``name`` around the ``with`` body; yields its ``args``,
        to which the body may add what it learns."""
        from jax.profiler import TraceAnnotation

        args = {k: v for k, v in args.items() if v is not None}
        idx = len(self.spans)
        rec = Span(name, time.perf_counter_ns(), -1,
                   self._open[-1] if self._open else None, args)
        self.spans.append(rec)
        self._open.append(idx)
        try:
            with TraceAnnotation(name, **args):
                yield args
        finally:
            rec.t1_ns = time.perf_counter_ns()
            self._open.pop()

    def count(self, name: str, **values: float) -> None:
        self.counters.append(Counter(name, time.perf_counter_ns(), values))

    def on_event(self, event: str, duration: float, **_: Any) -> None:
        """``jax.monitoring`` duration listener: compiles and cache loads."""
        kind = COMPILE_EVENTS.get(event)
        if kind is None:
            return
        t1 = time.perf_counter_ns()
        self.spans.append(Span(
            "repro.compile", t1 - int(duration * 1e9), t1,
            self._open[-1] if self._open else None,
            {"event": kind, "duration_s": float(duration)}))
        self.compiles += 1
        self.count("repro.compile", compiles=self.compiles)

    # -- reading -------------------------------------------------------------
    def named(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name]

    def save(self, path: str) -> None:
        """Write the spans as complete events and the counters as counter
        events of one process, in microseconds from the recording's
        start, as Chrome trace JSON."""
        out = TraceRecorder()
        out._meta(1, 1, "process_name", "repro serving")
        us = lambda ns: (ns - self.origin_ns) / 1e3
        for s in self.spans:
            if s.t1_ns < 0:
                continue
            out.events.append({
                "ph": "X", "name": s.name, "cat": s.name.split(".")[1],
                "pid": 1, "tid": 1, "ts": us(s.t0_ns),
                "dur": (s.t1_ns - s.t0_ns) / 1e3, "args": s.args})
        for c in self.counters:
            out.events.append({"ph": "C", "name": c.name, "pid": 1,
                               "ts": us(c.t_ns), "args": c.values})
        out.save(path)


def start() -> SpanRecorder:
    """Turn a fresh recorder on (replacing any other) and return it."""
    global RECORDER
    import jax

    stop()
    rec = SpanRecorder()
    jax.monitoring.register_event_duration_secs_listener(rec.on_event)
    RECORDER = rec
    return rec


def stop() -> Optional[SpanRecorder]:
    """Turn the recorder off and return it."""
    global RECORDER
    rec, RECORDER = RECORDER, None
    if rec is not None:
        import jax

        jax.monitoring.unregister_event_duration_listener(rec.on_event)
    return rec


@contextlib.contextmanager
def recording() -> Iterator[SpanRecorder]:
    rec = start()
    try:
        yield rec
    finally:
        stop()


def request_latencies(rec: SpanRecorder
                      ) -> Tuple[Dict[int, float], List[float]]:
    """Each request's time to first token, from its submit, and every gap
    between consecutive tokens, in seconds.

    A request's first token is read back at the end of its
    ``repro.batcher.admit`` span; after that it gains one token at the
    end of the ``repro.batcher.sample`` span of every step, the step that
    admitted it included, until the ``repro.batcher.update`` span that
    lists it as finished.
    """
    by_step: Dict[int, List[Span]] = {}
    for s in rec.spans:
        if s.parent is not None:
            by_step.setdefault(s.parent, []).append(s)
    ttft: Dict[int, float] = {}
    last: Dict[int, int] = {}
    gaps: List[float] = []
    for i, step in enumerate(rec.spans):
        if step.name != "repro.batcher.step":
            continue
        for s in by_step.get(i, []):
            if s.name == "repro.batcher.admit":
                rid = s.args["rid"]
                last[rid] = s.t1_ns
                if "queued_ns" in s.args:
                    ttft[rid] = (s.t1_ns - s.t0_ns + s.args["queued_ns"]) / 1e9
            elif s.name == "repro.batcher.sample":
                for rid, t in last.items():
                    gaps.append((s.t1_ns - t) / 1e9)
                    last[rid] = s.t1_ns
            elif s.name == "repro.batcher.update":
                for rid in s.args.get("finished", ()):
                    last.pop(rid, None)
    return ttft, gaps
