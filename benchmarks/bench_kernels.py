"""Kernel micro-benchmarks of the jitted jnp references on the host CPU,
plus interpret-mode correctness deltas. Host-only: these times say
nothing about the chip. On a TPU backend ``kernels.ops`` runs the
compiled Pallas kernels instead; no setting selects them."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, header, time_fn
from repro.kernels import ref


def run() -> None:
    header("kernels: host reference-path timings")
    rng = np.random.default_rng(0)

    b, s, hq, hkv, d = 1, 1024, 8, 2, 64
    q = jnp.asarray(rng.standard_normal((b, s, hq, d)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((b, s, hkv, d)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((b, s, hkv, d)), jnp.bfloat16)
    f = jax.jit(lambda q, k, v: ref.attention_ref(q, k, v, causal=True))
    us = time_fn(f, q, k, v, iters=3)
    flops = 4 * b * hq * s * s * d
    emit("kern/attention_1k", us, f"gflops_s={flops/(us*1e-6)/1e9:.1f}")

    qd = jnp.asarray(rng.standard_normal((8, hq, d)), jnp.bfloat16)
    kd = jnp.asarray(rng.standard_normal((8, hkv, 4096, d)), jnp.bfloat16)
    vd = jnp.asarray(rng.standard_normal((8, hkv, 4096, d)), jnp.bfloat16)
    length = jnp.full((8,), 4096, jnp.int32)
    fd = jax.jit(lambda q, k, v, l: ref.decode_attention_ref(q, k, v, l))
    us = time_fn(fd, qd, kd, vd, length, iters=3)
    emit("kern/decode_attention_4k", us,
         f"gb_s={(kd.nbytes+vd.nbytes)/(us*1e-6)/1e9:.1f}")

    m, kk, n = 512, 1024, 512
    x = jnp.asarray(rng.standard_normal((m, kk)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((kk, n)), jnp.float32)
    xq, sx = ref.quantize_int8(x, axis=1)
    wq, sw = ref.quantize_int8(w, axis=0)
    fi = jax.jit(ref.int8_matmul_ref)
    us = time_fn(fi, xq, sx, wq, sw, iters=3)
    emit("kern/int8_matmul_512", us,
         f"gops_s={2*m*kk*n/(us*1e-6)/1e9:.1f}")

    bs, ss, hh, pp, nn = 1, 2048, 4, 64, 128
    xs = jnp.asarray(rng.standard_normal((bs, ss, hh, pp)), jnp.float32)
    dts = jnp.asarray(rng.uniform(0.01, 0.2, (bs, ss, hh)), jnp.float32)
    A = jnp.asarray(-rng.uniform(0.5, 2, (hh,)), jnp.float32)
    B = jnp.asarray(rng.standard_normal((bs, ss, nn)), jnp.float32)
    C = jnp.asarray(rng.standard_normal((bs, ss, nn)), jnp.float32)
    D = jnp.ones((hh,), jnp.float32)
    fs = jax.jit(lambda *a: ref.ssd_chunked(*a, chunk=128))
    us = time_fn(fs, xs, dts, A, B, C, D, iters=3)
    emit("kern/ssd_chunked_2k", us, f"tokens_s={bs*ss/(us*1e-6):.0f}")

    xr = jnp.asarray(rng.standard_normal((4096, 1024)), jnp.bfloat16)
    wr = jnp.ones((1024,), jnp.float32)
    fr = jax.jit(lambda x, w: ref.rmsnorm_ref(x, w))
    us = time_fn(fr, xr, wr, iters=5)
    emit("kern/rmsnorm_4kx1k", us,
         f"gb_s={2*xr.nbytes/(us*1e-6)/1e9:.1f}")


if __name__ == "__main__":
    run()
