"""Training launcher.

Smoke-scale (CPU) end-to-end runs use ``--smoke``; a full-width train
step (2 layers) is compiled for a v5e chip by ``tests/test_tpu_compile.py``.
Demonstrates checkpoint/resume (kill and re-run with the same
--ckpt-dir) and straggler-hedged data loading.
"""
from __future__ import annotations

import argparse
import json
import logging

from repro.config import ModelConfig, TrainConfig, get_config, smoke_config
from repro.training.data import DataConfig, PrefetchingLoader
from repro.training.train_loop import Trainer


def default_train_config(cfg: ModelConfig) -> TrainConfig:
    """Per-arch training policy: bigger models get full remat, gradient
    accumulation, and int8 Adam moments (the state-compression trick that
    lets the 398B/778B configs approach 16 GB/chip HBM)."""
    n = cfg.num_params
    big = n > 30e9
    if n > 100e9:
        mb = 16
    elif n > 3e9:
        mb = 8
    else:
        mb = 1
    return TrainConfig(
        # 4k-seq training materializes O(s^2) attention scores on the
        # reference path — remat pays for itself from ~0.1B up.
        remat="full" if n > 0.1e9 else "none",
        scan_layers=True,
        opt_state_dtype="int8" if big else "fp32",
        microbatches=mb,
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--opt-state-dtype", default="fp32",
                    choices=["fp32", "int8"])
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(levelname)s %(message)s")
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
    base = default_train_config(cfg)
    tcfg = TrainConfig(**{**base.__dict__,
                          "learning_rate": args.lr,
                          "total_steps": args.steps,
                          "warmup_steps": max(args.steps // 10, 1),
                          "opt_state_dtype": args.opt_state_dtype,
                          "microbatches": 1 if args.smoke else base.microbatches,
                          "remat": "none" if args.smoke else base.remat})
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq_len,
                      global_batch=args.batch,
                      frontend_tokens=cfg.frontend_tokens,
                      frontend_dim=cfg.frontend_dim or cfg.d_model)
    loader = PrefetchingLoader(dcfg)
    trainer = Trainer(cfg, tcfg, ckpt_dir=args.ckpt_dir,
                      ckpt_every=args.ckpt_every)
    hist = trainer.run(loader, steps=args.steps, log_every=args.log_every)
    print(json.dumps({
        "arch": args.arch,
        "steps": len(hist["loss"]),
        "first_loss": hist["loss"][0],
        "last_loss": hist["loss"][-1],
        "mean_step_s": sum(hist["step_time_s"]) / len(hist["step_time_s"]),
        "hedged_batches": loader.hedge_count,
    }, indent=1))


if __name__ == "__main__":
    main()
