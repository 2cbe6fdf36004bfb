"""Serving launcher: continuous-batched generation through the
:class:`~repro.runtime.ClusterRuntime` request-lifecycle API (activation
gating + energy accounting, paper §5.2).

    PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b --smoke

:func:`serve` is the library form; ``chip_smoke.py`` calls it at full width.
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

from repro.config import (ModelConfig, ServeConfig, get_config, smoke_config,
                          use_compile_cache)
from repro.core.cluster import tpu_v5e_pod
from repro.runtime import ClusterRuntime, LMServingWorkload, ScalePolicy
from repro.serving.engine import ServingEngine


def serve(cfg: ModelConfig, prompts: Sequence[np.ndarray], *,
          max_new_tokens: int = 16, slots: int = 4,
          int8_weights: bool = False, seed: int = 0
          ) -> Tuple[Dict[str, Any], List[List[int]]]:
    """Serve ``prompts`` on random weights made from ``seed``.

    Returns the JSON report and each prompt's generated tokens, in the
    order of ``prompts`` (empty for a request that was not served).
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
    rids = [runtime.submit(np.asarray(p, np.int32)) for p in prompts]
    tel = runtime.run(max_ticks=10000)
    dt = time.monotonic() - t0
    by_rid = {r.rid: [int(t) for t in r.output] for r in tel.responses}
    outputs = [by_rid.get(rid, []) for rid in rids]
    tokens = sum(len(o) for o in outputs)
    report = {
        "arch": cfg.name,
        "requests": len(prompts),
        "served": tel.served,
        "ticks": tel.ticks,
        "wall_s": dt,
        "tokens_generated": tokens,
        "tokens_per_s": tokens / dt,
        "telemetry": {
            "mean_active_units": tel.mean_active,
            "energy_j_modeled": tel.energy_j,
            "tpe": tel.tpe,
            "scale_events": tel.scale_events,
            "p99_latency_ticks": tel.p99_latency_s,
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
    args = ap.parse_args()

    use_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=args.prompt_len)
               for _ in range(args.requests)]
    report, _ = serve(cfg, prompts, max_new_tokens=args.max_new_tokens,
                      slots=args.slots, int8_weights=args.int8_weights)
    print(json.dumps(report, indent=1))


if __name__ == "__main__":
    main()
