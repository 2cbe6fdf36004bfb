"""Fig 13 — SoC-collaborative DL inference: tensor parallelism with and
without compute/communication pipelining, plus the TPU ring-overlap
mapping, plus a real multi-device compute-scaling measurement."""
from __future__ import annotations

import subprocess
import sys
import os

import numpy as np

from benchmarks.common import emit, header
from repro.core.collaborative import (PAPER_FIG13, RESNET50_PROFILE, SOC_TCP,
                                      TPU_ICI, latency_breakdown)
from repro.core.cluster import soc_cluster
from repro.runtime import ClusterRuntime, QueueWorkload, ScalePolicy


def _runtime_section() -> None:
    header("fig13: collaborative serving through ClusterRuntime")
    # n SoCs collaborate per request (tensor parallel); a request takes
    # total_ms on its group, so each *unit* contributes (1000/total)/n
    # req/s. group_units=n makes the runtime activate whole collaboration
    # groups only — no SoC is stranded in a partial group.
    spec = soc_cluster()
    for n in (1, 2, 5):
        pipe = latency_breakdown(RESNET50_PROFILE, n, SOC_TCP,
                                 pipelined=True)
        unit_rate = 1000.0 / pipe["total_ms"] / n
        workload = QueueWorkload(unit_rate=unit_rate,
                                 name=f"collab-resnet50/n{n}",
                                 kind="collaborative")
        runtime = ClusterRuntime(spec, workload,
                                 policy=ScalePolicy(cooldown_s=30.0,
                                                    min_units=n),
                                 group_units=n)
        trace = np.full(300, 0.3 * unit_rate * spec.n_units)
        tel = runtime.play_trace(trace, dt_s=1.0)
        emit(f"fig13/runtime_n{n}", 0.0,
             f"tpe={tel.tpe:.3f};mean_active={tel.mean_active:.1f}"
             f"/{spec.n_units};p99_s={tel.p99_latency_s:.2f}")


def run(executable: bool = True) -> None:
    header("fig13: collaborative inference latency breakdown (model)")
    for n in range(1, 6):
        base = latency_breakdown(RESNET50_PROFILE, n, SOC_TCP)
        pipe = latency_breakdown(RESNET50_PROFILE, n, SOC_TCP,
                                 pipelined=True)
        ring = latency_breakdown(RESNET50_PROFILE, n, TPU_ICI,
                                 ring_overlap=True)
        emit(f"fig13/n{n}", 0.0,
             f"base_total={base['total_ms']:.1f}ms"
             f";base_comm_share={base['comm_share']:.3f}"
             f";pipelined_total={pipe['total_ms']:.1f}ms"
             f";pipelined_comm_share={pipe['comm_share']:.3f}"
             f";tpu_ring_total={ring['total_ms']:.2f}ms")
    emit("fig13/paper_reference", 0.0,
         f"comm_share@5={PAPER_FIG13['comm_share_at_5']}"
         f";pipelined={PAPER_FIG13['comm_share_at_5_pipelined']}"
         f";speedup@5={PAPER_FIG13['total_speedup_at_5']}")

    _runtime_section()

    if executable:
        header("fig13: executable TP compute scaling (fake devices)")
        code = """
import jax, jax.numpy as jnp, numpy as np, time
from repro.core.collaborative import make_tp_block
from repro.launch.mesh import make_mesh
import sys
n = int(sys.argv[1])
mesh = make_mesh((n,), ("model",))
rng = np.random.default_rng(0)
m, d, f = 64, 512, 2048
x = jnp.asarray(rng.standard_normal((m, d)), jnp.float32)
w1 = jnp.asarray(rng.standard_normal((d, f)), jnp.float32) * 0.05
w2 = jnp.asarray(rng.standard_normal((f, d)), jnp.float32) * 0.05
for overlap in (False, True):
    fn = make_tp_block(mesh, d, f, overlap=overlap)
    out = fn(x, w1, w2); jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(10):
        out = fn(x, w1, w2)
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / 10
    print(f"RESULT n={n} overlap={overlap} us={dt*1e6:.0f}")
"""
        # the children measure fake host devices; a chip, if there is
        # one, belongs to this process
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env["PYTHONPATH"] = os.path.join(here, "src") + os.pathsep + \
            env.get("PYTHONPATH", "")
        for n in (1, 2, 4):
            env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
            try:
                r = subprocess.run([sys.executable, "-c", code, str(n)],
                                   env=env, capture_output=True, text=True,
                                   timeout=300)
                for line in r.stdout.splitlines():
                    if line.startswith("RESULT"):
                        parts = dict(kv.split("=") for kv in
                                     line.split()[1:])
                        emit(f"fig13/exec_n{parts['n']}_overlap_"
                             f"{parts['overlap']}", float(parts["us"]),
                             "tp_block_fwd")
            except subprocess.TimeoutExpired:  # pragma: no cover
                emit(f"fig13/exec_n{n}", 0.0, "timeout")


if __name__ == "__main__":
    run()
