"""Slot-based continuous batching.

Fixed B decode slots; finished slots are refilled from the queue without
draining the batch (per-slot sequence positions — the attention layer takes
a (b,) position vector). Prefill runs per-request at batch 1 and the fresh
cache is inserted into the batched cache at the slot index.
"""
from __future__ import annotations

import itertools
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import model as lm
from repro.obs import serving as obs
from repro.serving.engine import ServingEngine


@dataclass
class Request:
    rid: int
    prompt: np.ndarray               # (s,) int32
    max_new_tokens: int
    generated: List[int] = field(default_factory=list)
    done: bool = False
    submit_ns: Optional[int] = None  # stamped only while spans record


class ContinuousBatcher:
    def __init__(self, engine: ServingEngine, slots: int):
        self.engine = engine
        self.cfg = engine.cfg
        self.slots = slots
        self.queue: Deque[Request] = deque()   # O(1) FIFO admission
        self.active: List[Optional[Request]] = [None] * slots
        self.finished: List[Request] = []
        self.positions = np.zeros(slots, np.int64)
        self.tokens = np.zeros(slots, np.int64)
        self.caches = None
        self._rid = itertools.count()
        self._insert_fns: Dict[int, Any] = {}

        def _insert(caches, cache1, slot):
            def ins(big, small):
                return jax.lax.dynamic_update_index_in_dim(
                    big, small[0], slot, axis=0)
            # caches leaves: (nb, b, ...); cache1 leaves: (nb, 1, ...)
            return jax.tree.map(
                lambda big, small: jax.vmap(
                    lambda bg, sm: jax.lax.dynamic_update_index_in_dim(
                        bg, sm[0], slot, axis=0))(big, small),
                caches, cache1)

        self._insert_jit = jax.jit(_insert, static_argnums=(2,),
                                   donate_argnums=(0,))

    # ------------------------------------------------------------------
    def submit(self, prompt: np.ndarray, max_new_tokens: int = 16) -> int:
        rid = next(self._rid)
        req = Request(rid, np.asarray(prompt, np.int32), max_new_tokens)
        rec = obs.RECORDER
        if rec is not None:
            req.submit_ns = rec.now_ns()
        self.queue.append(req)
        return rid

    def _ensure_caches(self) -> None:
        if self.caches is None:
            self.caches = lm.init_caches(
                self.cfg, self.slots, self.engine.max_len)

    def _admit(self, max_slots: Optional[int] = None) -> int:
        """Prefill queued requests into free slots; returns how many."""
        limit = self.slots if max_slots is None else min(max_slots,
                                                         self.slots)
        busy = sum(a is not None for a in self.active)
        admitted = 0
        for slot in range(self.slots):
            if busy >= limit or not self.queue:
                break
            if self.active[slot] is not None:
                continue
            busy += 1
            req = self.queue.popleft()
            rec = obs.RECORDER
            with obs.OFF if rec is None else rec.span(
                    "repro.batcher.admit", rid=req.rid, slot=slot,
                    prompt_len=len(req.prompt),
                    queued_ns=None if req.submit_ns is None
                    else rec.now_ns() - req.submit_ns):
                logits, cache1 = self.engine.prefill(req.prompt)
                self._ensure_caches()
                self.caches = self._insert_jit(self.caches, cache1, slot)
                nxt = int(jnp.argmax(logits[0]))
            req.generated.append(nxt)
            self.active[slot] = req
            self.positions[slot] = len(req.prompt)
            self.tokens[slot] = nxt
            admitted += 1
        return admitted

    def step(self, max_slots: Optional[int] = None) -> int:
        """One engine tick: admit (up to ``max_slots`` concurrent — the
        runtime's activation gate) + one batched decode. Returns number
        of active slots. Requests already in flight keep decoding even if
        ``max_slots`` drops below the current occupancy; the cap throttles
        admission only."""
        rec = obs.RECORDER
        with obs.OFF if rec is None else rec.span(
                "repro.batcher.step") as args:
            admitted = self._admit(max_slots)
            live = [s for s in range(self.slots)
                    if self.active[s] is not None]
            if rec is not None:
                args.update(live=len(live),
                            positions=int(self.positions[live].sum()),
                            syncs=admitted + bool(live))
            if not live:
                return 0
            self._ensure_caches()
            logits, self.caches = self.engine.decode(
                self.tokens, self.caches, self.positions,
                np.array([a is not None for a in self.active]))
            with obs.OFF if rec is None else rec.span(
                    "repro.batcher.sample"):
                nxt = np.asarray(jnp.argmax(logits, axis=-1))
            with obs.OFF if rec is None else rec.span(
                    "repro.batcher.update") as upd:
                before = len(self.finished)
                for s in live:
                    req = self.active[s]
                    req.generated.append(int(nxt[s]))
                    self.positions[s] += 1
                    if len(req.generated) >= req.max_new_tokens:
                        req.done = True
                        self.active[s] = None
                        self.finished.append(req)
                    else:
                        self.tokens[s] = int(nxt[s])
                if rec is not None:
                    upd["finished"] = [r.rid for r in self.finished[before:]]
            return len(live)

    def run_to_completion(self, max_ticks: int = 10000) -> List[Request]:
        start = len(self.finished)
        for _ in range(max_ticks):
            if not self.queue and all(a is None for a in self.active):
                break
            self.step()
        return self.finished[start:]
