"""Serving launcher: continuous-batched generation through the
:class:`~repro.runtime.ClusterRuntime` request-lifecycle API (activation
gating + energy accounting, paper §5.2).

    PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b --smoke \
        --trace-out serve_trace.json

:func:`serve` is the library form; ``chip_smoke.py`` calls it at full width.
The run records the serving path's spans (:mod:`repro.obs.serving`): the
report's time to first token and gaps between tokens are read from them,
and ``--trace-out`` writes them as Chrome trace JSON (open it in
``ui.perfetto.dev``).
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.config import (ModelConfig, ServeConfig, get_config, smoke_config,
                          use_compile_cache)
from repro.core.cluster import tpu_v5e_pod
from repro.obs import serving as obs
from repro.runtime import ClusterRuntime, LMServingWorkload, ScalePolicy
from repro.serving.engine import ServingEngine


def _pcts(values: Sequence[float]) -> Tuple[Optional[float], Optional[float]]:
    if not len(values):
        return None, None
    p50, p95 = np.percentile(values, [50, 95])
    return float(p50), float(p95)


def serve(cfg: ModelConfig, prompts: Sequence[np.ndarray], *,
          max_new_tokens: int = 16, slots: int = 4,
          int8_weights: bool = False, seed: int = 0,
          trace_out: Optional[str] = None
          ) -> Tuple[Dict[str, Any], List[List[int]]]:
    """Serve ``prompts`` on random weights made from ``seed``.

    Returns the JSON report and each prompt's generated tokens, in the
    order of ``prompts`` (empty for a request that was not served).
    Latencies count compilation, which the report gives apart
    (``compiles``, ``compile_s``). ``trace_out`` names a file for the
    spans as Chrome trace JSON.
    """
    scfg = ServeConfig(
        max_seq_len=max(len(p) for p in prompts) + max_new_tokens + 8,
        quantize_weights=int8_weights)
    engine = ServingEngine(cfg, scfg)
    engine.init_random(seed)
    workload = LMServingWorkload(engine, slots=slots,
                                 max_new_tokens=max_new_tokens)
    # a "unit" sustains ~0.25 req/s at smoke scale: a burst of submissions
    # scales slots up, and the window decay scales them back down
    runtime = ClusterRuntime(tpu_v5e_pod(8), workload,
                             policy=ScalePolicy(min_units=1),
                             unit_rate=0.25)

    t0 = time.monotonic()
    with obs.recording() as rec:
        rids = [runtime.submit(np.asarray(p, np.int32)) for p in prompts]
        tel = runtime.run(max_ticks=10000)
    dt = time.monotonic() - t0
    if trace_out:
        rec.save(trace_out)
    by_rid = {r.rid: [int(t) for t in r.output] for r in tel.responses}
    outputs = [by_rid.get(rid, []) for rid in rids]
    ttft, gaps = obs.request_latencies(rec)
    ttft_p50, ttft_p95 = _pcts(list(ttft.values()))
    itl_p50, itl_p95 = _pcts(gaps)
    report = {
        "arch": cfg.name,
        "requests": len(prompts),
        "served": tel.served,
        "ticks": tel.ticks,
        "wall_s": dt,
        "tokens_generated": sum(len(o) for o in outputs),
        "ttft_p50_s": ttft_p50,
        "ttft_p95_s": ttft_p95,
        "itl_p50_s": itl_p50,
        "itl_p95_s": itl_p95,
        "compiles": rec.compiles,
        "compile_s": sum(s.args["duration_s"]
                         for s in rec.named("repro.compile")),
        "telemetry": {
            "mean_active_units": tel.mean_active,
            "energy_j_modeled": tel.energy_j,
            "tpe": tel.tpe,
            "scale_events": tel.scale_events,
        },
        "sample_output": outputs[0][:8],
    }
    return report, outputs


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--int8-weights", action="store_true")
    ap.add_argument("--trace-out", metavar="PATH",
                    help="write the serving spans as Chrome trace JSON")
    args = ap.parse_args()

    use_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=args.prompt_len)
               for _ in range(args.requests)]
    report, _ = serve(cfg, prompts, max_new_tokens=args.max_new_tokens,
                      slots=args.slots, int8_weights=args.int8_weights,
                      trace_out=args.trace_out)
    print(json.dumps(report, indent=1))


if __name__ == "__main__":
    main()
