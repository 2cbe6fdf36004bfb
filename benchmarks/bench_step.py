"""Per-arch smoke-scale step timings (train + decode) on this host."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import emit, header
from repro.config import ServeConfig, TrainConfig, get_config, smoke_config
from repro.models import model as lm
from repro.serving.engine import ServingEngine
from repro.training.optimizer import init_opt_state
from repro.training.train_loop import make_train_step

ARCHS = ["internlm2-1.8b", "granite-moe-1b-a400m", "mamba2-130m",
         "jamba-1.5-large-398b", "musicgen-large", "internvl2-1b"]


def run(archs=None) -> None:
    header("steps: smoke-scale train/decode timings")
    for arch in archs or ARCHS:
        cfg = smoke_config(get_config(arch))
        tcfg = TrainConfig(remat="none", scan_layers=True)
        step = jax.jit(make_train_step(cfg, tcfg), donate_argnums=(0, 1))
        params = lm.init_params(cfg, jax.random.key(0))
        opt = init_opt_state(params, tcfg)
        b, s = 4, 64
        batch = {"tokens": jnp.ones((b, s), jnp.int32),
                 "labels": jnp.ones((b, s), jnp.int32),
                 "mask": jnp.ones((b, s), jnp.float32)}
        if cfg.frontend_tokens:
            batch["vision_embeds"] = jnp.zeros(
                (b, cfg.frontend_tokens, cfg.frontend_dim or cfg.d_model),
                jnp.float32)

        def run_step(p, o):
            p2, o2, m = step(p, o, batch)
            return m["loss"]

        # avoid donation invalidation during timing: copy each iter
        import time as _t
        p, o, m = step(params, opt, batch)
        jax.block_until_ready(m["loss"])
        t0 = _t.perf_counter()
        p, o, m = step(p, o, batch)
        jax.block_until_ready(m["loss"])
        us = (_t.perf_counter() - t0) * 1e6
        tokens = b * (s + cfg.frontend_tokens)
        emit(f"step/train_{arch}", us,
             f"tokens_s={tokens/(us*1e-6):.0f}")

        eng = ServingEngine(cfg, ServeConfig(max_seq_len=64))
        eng.init_random(0)
        lg, caches = eng.prefill_fn(eng.params,
                                    {"tokens": jnp.ones((2, 16), jnp.int32)})
        tok = jnp.ones((2, 1), jnp.int32)
        lg2, caches = eng.decode_fn(eng.params, tok, caches,
                                    jnp.full((2,), 16, jnp.int32))
        jax.block_until_ready(lg2)
        t0 = _t.perf_counter()
        lg2, caches = eng.decode_fn(eng.params, tok, caches,
                                    jnp.full((2,), 17, jnp.int32))
        jax.block_until_ready(lg2)
        us = (_t.perf_counter() - t0) * 1e6
        emit(f"step/decode_{arch}", us, f"tokens_s={2/(us*1e-6):.0f}")


if __name__ == "__main__":
    run()
