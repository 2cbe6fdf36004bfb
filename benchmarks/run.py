"""Benchmark driver: one section per paper table/figure + framework
benches. Prints ``name,us_per_call,derived`` CSV; ``--json PATH``
additionally writes a machine-readable record (per-suite wall times,
emitted rows, numeric metrics) that ``benchmarks/perf_gate.py`` compares
against the committed ``benchmarks/BENCH_baseline.json`` in CI."""
from __future__ import annotations

import argparse
import json
import platform
import sys
import time
import traceback
from typing import Optional

try:
    import resource
except ImportError:  # pragma: no cover - non-POSIX platforms
    resource = None  # type: ignore[assignment]


def _peak_rss_kb() -> Optional[int]:
    """Process peak RSS in KiB (Linux ``ru_maxrss`` units); a monotone
    high-water mark, so per-suite values attribute *growth*, not
    isolated usage. None where ``resource`` is unavailable."""
    if resource is None:
        return None
    return int(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset (e.g. fig6,table4)")
    ap.add_argument("--backend", default=None,
                    help="fleet engine for the fleet-driving suites "
                         "(scalar|vector|jax; default: each suite's own)")
    ap.add_argument("--fast", action="store_true",
                    help="skip host-executed model measurements")
    ap.add_argument("--list", action="store_true",
                    help="print registered suite names and exit")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write machine-readable results to PATH")
    args = ap.parse_args()

    from repro.config import use_compile_cache
    use_compile_cache()
    from benchmarks import (bench_kernels, bench_pool, bench_step, common,
                            fig6_transcoding, fig7_proportionality,
                            fig8_hw_codec, fig11_dl_serving,
                            fig12_dl_proportionality, fig13_collaborative,
                            fig14_mixed_tenancy, fig15_dvfs_pareto,
                            fig16_fleet, table2_microbench,
                            table3_network_bound, table4_tco, table5_tpc)

    suites = {
        "table2": table2_microbench.run,
        "table3": table3_network_bound.run,
        "fig6": fig6_transcoding.run,
        "fig7": fig7_proportionality.run,
        "fig8": fig8_hw_codec.run,
        "fig11": (lambda: fig11_dl_serving.run(measure=not args.fast)),
        "fig12": fig12_dl_proportionality.run,
        "fig13": (lambda: fig13_collaborative.run(
            executable=not args.fast)),
        "fig14": fig14_mixed_tenancy.run,
        "fig15": fig15_dvfs_pareto.run,
        "fig16": (lambda: fig16_fleet.run(perf=not args.fast,
                                          backend=args.backend)),
        "table4": table4_tco.run,
        "table5": table5_tpc.run,
        "kernels": bench_kernels.run,
        "steps": bench_step.run,
        "pool": bench_pool.run,
    }
    if args.list:
        for name in suites:
            print(name)
        return
    selected = (args.only.split(",") if args.only else list(suites))
    unknown = [name for name in selected if name not in suites]
    if unknown:
        sys.exit(f"unknown suite(s): {', '.join(unknown)}\n"
                 f"valid suites: {', '.join(suites)}")
    backends = ("scalar", "vector", "jax")
    if args.backend is not None and args.backend not in backends:
        sys.exit(f"unknown backend: {args.backend}\n"
                 f"valid backends: {', '.join(backends)}")
    record = common.start_json_recording() if args.json else None
    print("name,us_per_call,derived")
    failures = []
    for name in selected:
        common.begin_suite(name)
        t0 = time.perf_counter()
        ok = True
        try:
            suites[name]()
        except Exception as e:  # noqa: BLE001
            ok = False
            failures.append((name, repr(e)))
            traceback.print_exc()
            print(f"{name}/FAILED,0.0,{e!r}")
        finally:
            common.end_suite(name, time.perf_counter() - t0, ok,
                             peak_rss_kb=_peak_rss_kb())
    if record is not None:
        record["meta"] = {
            "python": platform.python_version(),
            "machine": platform.machine(),
            "suites_run": selected,
            "peak_rss_kb": _peak_rss_kb(),
        }
        with open(args.json, "w") as fh:
            json.dump(record, fh, indent=2, sort_keys=True)
            fh.write("\n")
        print(f"# wrote {args.json}")
    if failures:
        sys.exit(f"benchmark suites failed: {[f[0] for f in failures]}")


if __name__ == "__main__":
    main()
