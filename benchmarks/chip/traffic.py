"""The one traffic generator: reads a mix file from ``traffic/`` and makes
the requests of a run.

A mix file is JSON:

* ``arrivals``: ``{"kind": "poisson", "rate_per_s": r}``, open loop;
* ``knee_rps``: the highest rate the cell sustained in a sweep on the
  chip. The runtime's ``unit_rate`` is this over the slots;
* ``prompt``: a ``grid`` of lengths and a ``lognormal`` ``{median,
  sigma}`` whose mass is binned onto the grid;
* ``output``: ``lognormal`` ``{median, sigma}`` with ``min``/``max``;
* ``block``: requests per stratum. Every block holds the same multiset
  of prompt lengths, output lengths and inter-arrival gaps;
* ``schedule_seed``: draws the order of each block's lengths and gaps.
  The schedule is the cell's, the same in every run: ``--seed`` draws
  only the prompts' tokens (and the weights), so seeds do not change
  the work or when it arrives;
* ``slots``, ``max_seq_len``: the deployment that serves the mix;
* ``lead_in_s``, ``trace_s``, ``check``: read by the harness.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from statistics import NormalDist
from typing import List, Optional

import numpy as np

_N = NormalDist()


@dataclass
class Req:
    prompt: np.ndarray           # (s,) int32
    max_new: int
    due_s: float                 # due time from the first arrival of the
    #   lead-in
    # filled in by the harness
    submit_s: Optional[float] = None
    token_s: List[float] = field(default_factory=list)
    prefill_s: Optional[float] = None
    handle: object = None        # the batcher's request object


def grid_weights(prompt: dict) -> List[float]:
    grid = prompt["grid"]
    mu = math.log(prompt["lognormal"]["median"])
    sigma = prompt["lognormal"]["sigma"]
    edges = [0.0] + [math.sqrt(a * b) for a, b in zip(grid, grid[1:])] \
        + [math.inf]
    cdf = [0.0 if e == 0 else 1.0 if e == math.inf
           else _N.cdf((math.log(e) - mu) / sigma) for e in edges]
    w = [hi - lo for lo, hi in zip(cdf, cdf[1:])]
    total = sum(w)
    return [x / total for x in w]


def allocate(weights: List[float], n: int) -> List[int]:
    """Largest-remainder counts of ``n`` items over ``weights``."""
    raw = [w * n for w in weights]
    counts = [int(x) for x in raw]
    order = sorted(range(len(raw)), key=lambda i: counts[i] - raw[i])
    for i in order[: n - sum(counts)]:
        counts[i] += 1
    return counts


def block_prompt_lengths(prompt: dict, n: int) -> List[int]:
    counts = allocate(grid_weights(prompt), n)
    return [g for g, c in zip(prompt["grid"], counts) for _ in range(c)]


def block_output_lengths(output: dict, n: int) -> List[int]:
    us = [(j + 0.5) / n for j in range(n)]
    med, sigma = output["lognormal"]["median"], output["lognormal"]["sigma"]
    return [int(min(output["max"], max(output["min"], round(
        med * math.exp(sigma * _N.inv_cdf(u)))))) for u in us]


def block_gaps(rate: float, n: int) -> List[float]:
    return [-math.log(1.0 - (j + 0.5) / n) / rate for j in range(n)]


def make_requests(mix: dict, seed: int, vocab: int, count: int
                  ) -> List[Req]:
    """``count`` requests (rounded up to whole blocks): the cell's
    schedule, with prompt tokens drawn from ``seed``."""
    order = np.random.default_rng(mix["schedule_seed"])
    tokens = np.random.default_rng(seed)
    b = mix["block"]
    prompts = block_prompt_lengths(mix["prompt"], b)
    outputs = block_output_lengths(mix["output"], b)
    gaps = block_gaps(mix["arrivals"]["rate_per_s"], b)
    reqs: List[Req] = []
    t = 0.0
    for _ in range(-(-count // b)):
        p = order.permutation(prompts)
        o = order.permutation(outputs)
        g = order.permutation(gaps)
        for j in range(b):
            reqs.append(Req(
                prompt=tokens.integers(0, vocab, size=int(p[j]),
                                       dtype=np.int32),
                max_new=int(o[j]), due_s=t))
            t += float(g[j])
    return reqs


def max_output(mix: dict) -> int:
    return mix["output"]["max"]


def request_count(mix: dict, horizon_s: float) -> int:
    """Requests a run can use over ``horizon_s`` seconds."""
    return (int(math.ceil(mix["arrivals"]["rate_per_s"] * horizon_s))
            + mix["block"])
