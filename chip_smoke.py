"""Smoke test on one TPU: the Pallas kernels against their references, a
training step through them, then internlm2-1.8b served at full width
through the launcher's own code.

    python chip_smoke.py [--seed N]

Phases:

a. report the device; stop with an error unless it is a TPU;
b. run the compiled kernels at internlm2-1.8b widths (and the SSD scan at
   mamba2-130m widths) and compare each with its ``ref.*`` oracle on the
   same device;
c. take one training step of internlm2-1.8b at full width, depth cut to
   2 layers, on an unaligned sequence length: the kernels run forward and
   their references backward. Compare its loss and gradient norm with the
   same step on the jnp paths, on the same device;
d. serve 8 requests of seeded prompt lengths, 32 new tokens each, on 4
   slots, with random weights made from the seed, through
   ``repro.launch.serve.serve``; check token counts, the vocabulary bound,
   and that the same prompt served twice gives the same greedy output;
e. print the result as one JSON object on the last line.

Any failure exits non-zero without that line. Times printed are those of a
smoke run, compilation included, and are not measurements.
"""
from __future__ import annotations

import argparse
import itertools
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ARCH = "internlm2-1.8b"
# Largest kernel-vs-reference error allowed, as max|out - ref| / max|ref|.
# bf16 outputs round at 2^-9 relative; a wrong mask or tile is O(1).
ERR_BOUND = 2e-2
REQUESTS, NEW_TOKENS, SLOTS = 8, 32, 4
TRAIN_LAYERS, TRAIN_BATCH, TRAIN_SEQ = 2, 2, 300


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def phase_device() -> dict:
    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        fail(f"JAX's device is {dev.platform!r}, not a TPU; nothing was run")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def _check(name: str, out, want) -> None:
    out = np.asarray(out, np.float32)
    want = np.asarray(want, np.float32)
    if out.shape != want.shape:
        fail(f"kernel {name}: shape {out.shape}, reference {want.shape}")
    if not np.isfinite(out).all():
        fail(f"kernel {name}: non-finite output")
    err = float(np.max(np.abs(out - want)) / max(np.max(np.abs(want)), 1e-30))
    print(f"kernel {name}: shape={out.shape} max_err={err:.3e} "
          f"(bound {ERR_BOUND})", flush=True)
    if err > ERR_BOUND:
        fail(f"kernel {name}: error {err:.3e} above {ERR_BOUND}")


def phase_kernels(seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.config import get_config
    from repro.kernels import ops, ref
    from repro.kernels.decode_attention import decode_attention
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.rmsnorm import rmsnorm
    from repro.kernels.ssd_scan import ssd_scan

    if not ops.on_tpu():
        fail("kernels.ops does not select the Pallas kernels on this device")
    cfg = get_config(ARCH)
    hq, hkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    bf16 = jnp.bfloat16
    base = jax.random.key(seed)
    keys = (jax.random.fold_in(base, i) for i in itertools.count())

    def normal(shape, dtype=bf16):
        return jax.random.normal(next(keys), shape, jnp.float32).astype(dtype)

    exact = jax.default_matmul_precision("float32")
    # prefill attention: one block-aligned length, and one that ops pads
    for s in (512, 300):
        q, k, v = normal((1, s, hq, d)), normal((1, s, hkv, d)), \
            normal((1, s, hkv, d))
        out = flash_attention(q, k, v) if s % 128 == 0 else \
            ops.attention(q, k, v)
        with exact:
            want = ref.attention_ref(q, k, v)
        _check(f"flash_attention s={s}" + ("" if s % 128 == 0
                                          else " (ops, padded)"), out, want)
    # decode attention over a 4-slot head-major cache of ops.cache_len(440)
    # = 512, stacked over two layers and read at the second
    skv = ops.cache_len(440)
    q, k, v = normal((4, hq, d)), normal((2, 4, hkv, skv, d)), \
        normal((2, 4, hkv, skv, d))
    length = jax.random.randint(next(keys), (4,), 1, skv + 1)
    out = decode_attention(q, k, v, length, 1)
    with exact:
        want = ref.decode_attention_ref(q, k, v, length, 1)
    _check(f"decode_attention b=4 skv={skv}", out, want)
    # rmsnorm on prefill rows (not a multiple of the row block) and decode
    w = 1.0 + 0.1 * normal((cfg.d_model,), jnp.float32)
    for rows in (300, 4):
        x = normal((rows, cfg.d_model))
        _check(f"rmsnorm rows={rows}", rmsnorm(x, w, eps=cfg.norm_eps),
               ref.rmsnorm_ref(x, w, cfg.norm_eps))
    # SSD scan at mamba2-130m widths
    m = get_config("mamba2-130m")
    mc = m.mamba
    h, p, n, s = mc.n_heads(m.d_model), mc.headdim, mc.d_state, 512
    x, B, C = normal((1, s, h, p)), normal((1, s, n)), normal((1, s, n))
    dt = jax.random.uniform(next(keys), (1, s, h), jnp.float32, 1e-3, 1e-1)
    A = -jax.random.uniform(next(keys), (h,), jnp.float32, 1.0, 16.0)
    D = jnp.ones((h,), jnp.float32)
    y, state = ssd_scan(x, dt, A, B, C, D, chunk=mc.chunk_size)
    with exact:
        y_ref, state_ref = ref.ssd_chunked(x, dt, A, B, C, D,
                                           chunk=mc.chunk_size)
    _check(f"ssd_scan y s={s} h={h}", y, y_ref)
    _check(f"ssd_scan state s={s} h={h}", state, state_ref)


def phase_train(seed: int) -> None:
    import dataclasses
    from unittest import mock

    import jax
    import jax.numpy as jnp

    from repro.config import TrainConfig, get_config
    from repro.kernels import ops
    from repro.models import model as lm
    from repro.training.optimizer import init_opt_state
    from repro.training.train_loop import jit_train_step

    cfg = dataclasses.replace(get_config(ARCH), num_layers=TRAIN_LAYERS)
    tcfg = TrainConfig(warmup_steps=1, remat="none")
    params = lm.init_params(cfg, jax.random.key(seed))
    opt = init_opt_state(params, tcfg)
    shape = (TRAIN_BATCH, TRAIN_SEQ)
    k_tok, k_lab = jax.random.split(jax.random.fold_in(jax.random.key(seed),
                                                       1))
    batch = {"tokens": jax.random.randint(k_tok, shape, 0, cfg.vocab_size),
             "labels": jax.random.randint(k_lab, shape, 0, cfg.vocab_size),
             "mask": jnp.ones(shape, jnp.int32)}

    def step():
        # The updated state is dropped on return: two of it do not fit.
        new, _, metrics = jit_train_step(cfg, tcfg, None, donate=False)(
            params, opt, batch)
        if not all(bool(jnp.isfinite(p).all()) for p in jax.tree.leaves(new)):
            fail("train step: non-finite parameters after the update")
        return {k: float(metrics[k]) for k in ("loss", "grad_norm")}

    t0 = time.monotonic()
    got = step()
    wall = time.monotonic() - t0
    with mock.patch.object(ops, "on_tpu", lambda: False):
        want = step()
    print(f"train step: {cfg.name} layers={cfg.num_layers} "
          f"d_model={cfg.d_model} batch={shape} wall_s={wall:.1f} "
          f"(smoke run incl. compilation; not a measurement)", flush=True)
    for k in ("loss", "grad_norm"):
        err = abs(got[k] - want[k]) / abs(want[k])
        print(f"train {k}: kernels {got[k]:.6f} jnp paths {want[k]:.6f} "
              f"rel_err={err:.3e} (bound {ERR_BOUND})", flush=True)
        if not np.isfinite(got[k]) or err > ERR_BOUND:
            fail(f"train step: {k} {got[k]} against {want[k]} on the jnp "
                 f"paths")


def phase_serve(seed: int) -> None:
    from repro.config import get_config
    from repro.launch.serve import serve

    cfg = get_config(ARCH)
    print(f"model: {cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
          f"heads={cfg.num_heads} kv_heads={cfg.num_kv_heads} "
          f"head_dim={cfg.resolved_head_dim} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab_size} dtype={cfg.dtype} "
          f"(full width, random weights from seed {seed})", flush=True)
    rng = np.random.default_rng(seed)
    lens = rng.integers(16, 400, size=REQUESTS - 1)
    if not (lens % 128).any():
        lens[0] += 1
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in lens]
    prompts.append(prompts[0].copy())       # the same prompt, served twice
    print(f"prompt lengths: {[len(p) for p in prompts]}", flush=True)
    t0 = time.monotonic()
    report, outputs = serve(cfg, prompts, max_new_tokens=NEW_TOKENS,
                            slots=SLOTS, seed=seed)
    wall = time.monotonic() - t0
    tokens = sum(len(o) for o in outputs)
    print(f"served={report['served']:g}/{REQUESTS} tokens={tokens} "
          f"wall_s={wall:.1f} (smoke run incl. compilation; "
          f"not a measurement)", flush=True)
    if report["served"] != REQUESTS:
        fail(f"served {report['served']} of {REQUESTS} requests")
    for i, out in enumerate(outputs):
        if len(out) != NEW_TOKENS:
            fail(f"request {i} got {len(out)} tokens, not {NEW_TOKENS}")
        if not all(0 <= t < cfg.vocab_size for t in out):
            fail(f"request {i} has a token id outside [0, {cfg.vocab_size})")
    if outputs[-1] != outputs[0]:
        fail(f"the same prompt gave two outputs: {outputs[0]} and "
             f"{outputs[-1]}")
    print(f"repeat prompt: identical greedy output {outputs[0][:8]}...",
          flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    src = os.path.join(HERE, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        fail(f"no repro package under {src}; run from a checkout")
    sys.path.insert(0, src)
    from repro.config import use_compile_cache

    print(f"compile cache: {use_compile_cache()}", flush=True)
    device = phase_device()
    phase_kernels(args.seed)
    phase_train(args.seed)
    phase_serve(args.seed)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
