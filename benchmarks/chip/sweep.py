"""Find a cell's knee: the highest offered rate whose backlog does not
grow over the window. One process sets up once and offers each rate in
turn, draining the server between rates.

    python3 benchmarks/chip/sweep.py --workload internlm2-1.8b.chat \
        --rates 2.5,3,3.5,4 --seconds 30 --seed 11

Prints one JSON line per rate: the queue at the window's start and end,
requests completed per second, time to first token and gap between
tokens. The runtime's ``unit_rate`` is the offered rate over the slots,
so the activation gate grants every slot.
"""
import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import harness
    import traffic

    c = harness.load_cell(args.workload)
    harness.cache_env()
    import jax

    from repro.config import use_compile_cache

    if jax.devices()[0].platform != "tpu":
        harness.log("the sweep measures a TPU; none found")
        return 3
    use_compile_cache()
    conf, mix = c["config"], c["mix"]
    fam = harness.family(conf)
    srv = harness.Server(fam.program_config(conf), mix, args.seed,
                         conf.get("serve", {}))
    srv.warm(mix["prompt"]["grid"])
    harness.log(f"set-up {time.time() - T_START:.1f} s")
    for rate in [float(r) for r in args.rates.split(",")]:
        m = dict(mix, arrivals={"kind": "poisson", "rate_per_s": rate})
        srv.runtime.governor.unit_rate = rate / srv.slots
        reqs = traffic.make_requests(
            m, args.seed, fam.vocab(conf),
            traffic.request_count(m, m["lead_in_s"] + args.seconds))
        w = harness.drive(srv, m, reqs, args.seconds, drain_s=0.0)
        inside = [t for t in w.ticks if w.lo <= t[0] < w.hi]
        e2e = harness.end_to_end(w, drain_s=0.0)
        done = sum(1 for r in reqs if r.handle is not None and r.handle.done
                   and r.token_s and w.lo <= r.token_s[-1] < w.hi)
        print(json.dumps({
            "rate": rate,
            "queued_start": inside[0][3] if inside else None,
            "queued_end": inside[-1][3] if inside else None,
            "completed_rps": done / args.seconds,
            "mean_active": sum(t[2] for t in inside) / max(len(inside), 1),
            "ttft_p90_ms": e2e["ttft_p90_ms"], "itl_p95_ms": e2e["itl_p95_ms"],
            "output_tokens_per_s": e2e["output_tokens_per_s"]}), flush=True)
        srv.batcher.queue.clear()
        srv.fifo.clear()
        while any(a is not None for a in srv.batcher.active):
            srv.batcher.step()
        srv.batcher.finished.clear()
        srv.spans.clear()
    return 0


if __name__ == "__main__":
    sys.exit(main())
