"""Run one benchmark cell once, on the chips of this machine.

    python3 benchmarks/chip/run.py --workload internlm2-1.8b.chat \
        --seed 7 --seconds 40 --trace 0

The cell, its configuration (``configs/``) and its traffic mix
(``traffic/``) are named in ``BENCHMARK.json`` at the root of the
checkout. The last line of standard output is the result as JSON; the
lines before it on standard error report the device, the requests, how
late the generator ran, compiles inside the window, peak memory and,
last, each number of the output check beside its limit. A machine
without the TPU the cell asks for exits with code 3 and no result.
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
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")

    import harness

    cell = harness.load_cell(args.workload)
    harness.cache_env()
    import jax

    import repro  # noqa: F401  the system under test, from ../../src

    devs = jax.devices()
    chips = cell["cell"]["chips"]
    if devs[0].platform != "tpu" or len(devs) < chips:
        harness.log(f"needs {chips} TPU chip(s); JAX found {len(devs)} "
                    f"{devs[0].platform} device(s)")
        return 3
    out = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                      T_START)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
