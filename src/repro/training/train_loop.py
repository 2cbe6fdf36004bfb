"""Train-step builder + fault-tolerant training loop.

``make_train_step`` closes over (model cfg, train cfg) and returns a pure
(params, opt_state, batch) -> (params, opt_state, metrics) function. All
sharding is injected by tracing under ``use_sharding(mesh, train_rules)``
— the same function lowers for 1 CPU device (smoke tests) and for a
multi-device mesh unchanged.
"""
from __future__ import annotations

import logging
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig, TrainConfig
from repro.distributed.sharding import (RuleSet, train_rules,
                                        use_sharding)
from repro.models import model as lm
from repro.training import checkpoint as ckpt
from repro.training.optimizer import (OptState, adamw_update,
                                      init_opt_state)

log = logging.getLogger(__name__)
Params = Any


def make_train_step(cfg: ModelConfig, tcfg: TrainConfig
                    ) -> Callable[[Params, OptState, Dict[str, Any]],
                                  Tuple[Params, OptState, Dict[str, Any]]]:
    def loss_fn(params, batch):
        return lm.loss_fn(params, cfg, batch, scan=tcfg.scan_layers,
                          remat=tcfg.remat, loss_chunk=tcfg.loss_chunk)

    def train_step(params, opt_state, batch):
        if tcfg.microbatches > 1:
            mb = tcfg.microbatches

            def split(x):
                b = x.shape[0]
                return x.reshape(mb, b // mb, *x.shape[1:])

            micro = jax.tree.map(split, batch)

            def accum(carry, mb_batch):
                g_acc, l_acc, m_acc = carry
                (loss, metrics), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, mb_batch)
                g_acc = jax.tree.map(jnp.add, g_acc, grads)
                return (g_acc, l_acc + loss, m_acc + metrics["ce"]), None

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, loss, ce), _ = jax.lax.scan(
                accum, (zeros, 0.0, 0.0), micro)
            grads = jax.tree.map(lambda g: g / mb, grads)
            loss, ce = loss / mb, ce / mb
            metrics = {"ce": ce}
        else:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
        params, opt_state, opt_metrics = adamw_update(
            grads, opt_state, params, tcfg)
        out = {"loss": loss, **metrics, **opt_metrics}
        return params, opt_state, out

    return train_step


def jit_train_step(cfg: ModelConfig, tcfg: TrainConfig, mesh, rules=None,
                   in_shardings=None, out_shardings=None, donate: bool = True):
    """Trace the train step under the sharding context and jit it."""
    rules = rules or train_rules()
    step = make_train_step(cfg, tcfg)

    def traced(params, opt_state, batch):
        with use_sharding(mesh, rules):
            return step(params, opt_state, batch)

    kwargs = {}
    if in_shardings is not None:
        kwargs["in_shardings"] = in_shardings
    if out_shardings is not None:
        kwargs["out_shardings"] = out_shardings
    if donate:
        kwargs["donate_argnums"] = (0, 1)
    return jax.jit(traced, **kwargs)


# ---------------------------------------------------------------------------
# The loop.
# ---------------------------------------------------------------------------
class Trainer:
    """Checkpointed, resumable training loop with async saves."""

    def __init__(self, cfg: ModelConfig, tcfg: TrainConfig, *,
                 mesh=None, rules: Optional[RuleSet] = None,
                 ckpt_dir: Optional[str] = None, ckpt_every: int = 50,
                 keep: int = 3):
        self.cfg, self.tcfg = cfg, tcfg
        self.mesh, self.rules = mesh, rules or train_rules()
        self.ckpt_dir = ckpt_dir
        self.ckpt_every = ckpt_every
        self.keep = keep
        self.step_fn = jit_train_step(cfg, tcfg, mesh, self.rules,
                                      donate=False)
        self._pending_save = None

    def init_state(self, seed: int = 0) -> Tuple[Params, OptState, int]:
        params = lm.init_params(self.cfg, jax.random.key(seed))
        opt_state = init_opt_state(params, self.tcfg)
        start = 0
        if self.ckpt_dir and ckpt.latest_step(self.ckpt_dir) is not None:
            start = ckpt.latest_step(self.ckpt_dir)
            tree = ckpt.restore(self.ckpt_dir,
                                {"params": params, "opt": opt_state})
            params, opt_state = tree["params"], tree["opt"]
            log.info("resumed from step %d", start)
        return params, opt_state, start

    def maybe_checkpoint(self, step: int, params: Params,
                         opt_state: OptState, force: bool = False) -> None:
        if not self.ckpt_dir:
            return
        if force or (step > 0 and step % self.ckpt_every == 0):
            if self._pending_save is not None:
                self._pending_save.wait()
            self._pending_save = ckpt.save_async(
                self.ckpt_dir, step, {"params": params, "opt": opt_state},
                keep=self.keep)

    def run(self, data_iter, steps: int, seed: int = 0,
            log_every: int = 10) -> Dict[str, list]:
        params, opt_state, start = self.init_state(seed)
        history: Dict[str, list] = {"step": [], "loss": [], "ce": [],
                                    "step_time_s": []}
        for step in range(start, steps):
            batch = data_iter.get(step) if hasattr(data_iter, "get") \
                else next(data_iter)
            t0 = time.monotonic()
            params, opt_state, metrics = self.step_fn(
                params, opt_state, batch)
            loss = float(metrics["loss"])
            dt = time.monotonic() - t0
            history["step"].append(step)
            history["loss"].append(loss)
            history["ce"].append(float(metrics["ce"]))
            history["step_time_s"].append(dt)
            if step % log_every == 0:
                log.info("step %d loss %.4f (%.2fs)", step, loss, dt)
            self.maybe_checkpoint(step + 1, params, opt_state)
        self.maybe_checkpoint(steps, params, opt_state, force=True)
        if self._pending_save is not None:
            self._pending_save.wait()
        history["params"] = params          # type: ignore[assignment]
        history["opt_state"] = opt_state    # type: ignore[assignment]
        return history
