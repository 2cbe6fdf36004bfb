"""Readings that set a cell's limit: the mean logit gap of the compared
served tokens in sound runs of the program over many seeds, and of the
control, the program with its int8 weight-only path switched on
(``ServeConfig.quantize_weights``, the precision below the
configuration's bfloat16), over a few seeds. Each is
a whole run of the cell, at its own traffic, load and size, through the
same comparison that decides ``correct``; one process sets up for each.

    python3 benchmarks/chip/control.py --workload internlm2-1.8b.chat \
        --seconds 30 --seeds 101-112 --control-seeds 201-203

Prints one JSON line per run with ``correct`` and its readings; the
lower reading is the largest over the sound seeds, the upper the
smallest over the control's, whose runs should read ``correct: false``.
"""
import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]


def seeds(text: str):
    out = []
    for part in filter(None, text.split(",")):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi or lo) + 1))
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args()

    import harness

    c = harness.load_cell(args.workload)
    harness.cache_env()
    import jax

    if jax.devices()[0].platform != "tpu":
        harness.log("the readings are taken on a TPU; none found")
        return 3
    runs = [(s, None) for s in seeds(args.seeds)] + [
        (s, {"quantize_weights": True}) for s in seeds(args.control_seeds)]
    for seed, serve in runs:
        out = harness.run(c, seed, args.seconds, False, time.time(),
                          serve=serve)
        print(json.dumps({"seed": seed, "control": serve is not None,
                          "correct": out["correct"], **out["readings"],
                          "metrics": {k: v["value"] for k, v
                                      in out["metrics"].items()}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
