"""Serving engine: jit'd prefill / decode with full-length caches.

Decode caches live at ``max_len`` from the start: ``ServeConfig.max_seq_len``
rounded up to the decode kernel's KV block (``ops.cache_len``). Prefill
writes the first ``s`` positions and the engine pads. Weight-only int8
serving (the paper's DSP path) is applied at load time via
``ServeConfig.quantize_weights``.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig, ServeConfig
from repro.distributed.sharding import RuleSet, serve_rules, use_sharding
from repro.kernels import ops
from repro.kernels.ref import quantize_int8
from repro.models import model as lm
from repro.obs import serving as obs

Params = Any


def quantize_params_int8(params: Params) -> Params:
    """Weight-only int8: store int8 payload + per-output-channel scales,
    dequantized on use. (Serving-only; halves/quarters weight HBM.)"""
    def q(leaf):
        if leaf.ndim >= 2 and leaf.dtype in (jnp.bfloat16, jnp.float32):
            qv, s = quantize_int8(leaf, axis=-2)  # per-column of last dim
            return {"__int8__": qv, "scale": s}
        return leaf
    return jax.tree.map(q, params)


def dequantize_params(params: Params) -> Params:
    def dq(leaf):
        if isinstance(leaf, dict) and "__int8__" in leaf:
            return (leaf["__int8__"].astype(jnp.float32)
                    * leaf["scale"][..., None, :]).astype(jnp.bfloat16)
        return leaf
    return jax.tree.map(dq, params,
                        is_leaf=lambda l: isinstance(l, dict)
                        and "__int8__" in l)




def recurrent_state_bytes(cfg: ModelConfig) -> int:
    """Bytes of recurrent state one slot holds: the conv window and the
    SSD state of every Mamba layer (0 for attention alone)."""
    caches = jax.eval_shape(lambda: lm.init_caches(cfg, 1, 1))
    return sum(leaf.size * leaf.dtype.itemsize
               for c in caches if c is not None and "ssd" in c
               for leaf in jax.tree.leaves(c))


class ServingEngine:
    def __init__(self, cfg: ModelConfig, scfg: Optional[ServeConfig] = None,
                 mesh=None, rules: Optional[RuleSet] = None,
                 scan: bool = True):
        self.cfg = cfg
        self.scfg = scfg or ServeConfig()
        self.mesh = mesh
        self.rules = rules or serve_rules(self.scfg.serve_fsdp)
        self.scan = scan
        self.max_len = ops.cache_len(self.scfg.max_seq_len)
        self.params: Optional[Params] = None
        self.state_bytes = recurrent_state_bytes(cfg)

        def _prefill(params, batch):
            with use_sharding(self.mesh, self.rules):
                if self.scfg.quantize_weights:
                    params = dequantize_params(params)
                return lm.prefill(params, cfg, batch, scan=self.scan,
                                  max_len=self.max_len)

        def _decode(params, tokens, caches, pos):
            # a finished slot is uploaded with the token -1 (:meth:`decode`)
            with use_sharding(self.mesh, self.rules):
                if self.scfg.quantize_weights:
                    params = dequantize_params(params)
                return lm.decode_step(params, cfg, tokens, caches, pos,
                                      scan=self.scan,
                                      live=tokens[:, 0] >= 0)

        self.prefill_fn = jax.jit(_prefill)
        self.decode_fn = jax.jit(_decode, donate_argnums=(2,))

    # ------------------------------------------------------------------
    def load(self, params: Params) -> None:
        if self.scfg.quantize_weights:
            params = quantize_params_int8(params)
        self.params = params

    def init_random(self, seed: int = 0) -> None:
        self.load(lm.init_params(self.cfg, jax.random.key(seed)))

    # ------------------------------------------------------------------
    def prefill(self, prompt: np.ndarray):
        """Upload one prompt and dispatch the prefill at batch 1; returns
        (last-position logits, caches). Its span carries ``state_bytes``,
        the recurrent state the prefill makes for its slot."""
        rec = obs.RECORDER
        with obs.OFF if rec is None else rec.span(
                "repro.engine.prefill", state_bytes=self.state_bytes or None):
            batch = {"tokens": jnp.asarray(prompt[None, :])}
            return self.prefill_fn(self.params, batch)

    def kv_blocks(self, positions: np.ndarray):
        """(blocks the decode kernel fetches per attention layer, blocks
        in the cache per layer) for slots at ``positions``: each slot's
        KV blocks up to the one holding its new position, of all
        ``max_len / block`` blocks; (None, None) without attention."""
        if not self.cfg.uses_attention:
            return None, None
        bk = min(ops.DECODE_BLOCK, self.max_len)
        lengths = np.asarray(positions, np.int64) + 1
        return (int(np.sum(-(-lengths // bk))),
                len(lengths) * (self.max_len // bk))

    def decode(self, tokens: np.ndarray, caches, positions: np.ndarray,
               live: Optional[np.ndarray] = None):
        """Upload each slot's last token and position and dispatch one
        decode step; returns (logits, caches). A slot that ``live`` ((b,)
        bool; None for all) marks finished is uploaded with the token -1:
        its recurrent state is left as it was and its logits are not to
        be read. Its span carries ``state_bytes``, the recurrent state of
        the live slots, which the step reads and writes back, of
        ``state_bytes_all``, that of every slot, and ``kv_blocks`` of
        ``kv_blocks_all`` (:meth:`kv_blocks`)."""
        rec = obs.RECORDER
        blocks, blocks_all = (None, None) if rec is None else \
            self.kv_blocks(positions)
        live = np.ones(len(tokens), bool) if live is None else live
        with obs.OFF if rec is None else rec.span(
                "repro.engine.decode",
                state_bytes=self.state_bytes * int(np.sum(live)) or None,
                state_bytes_all=self.state_bytes * len(tokens) or None,
                kv_blocks=blocks, kv_blocks_all=blocks_all):
            toks = jnp.asarray(np.where(live, tokens, -1)[:, None], jnp.int32)
            pos = jnp.asarray(positions, jnp.int32)
            return self.decode_fn(self.params, toks, caches, pos)

    # ------------------------------------------------------------------
    def generate(self, tokens: jax.Array, max_new_tokens: int,
                 vision_embeds: Optional[jax.Array] = None,
                 greedy: bool = True, rng: Optional[jax.Array] = None
                 ) -> jax.Array:
        """tokens: (b, s) -> (b, max_new_tokens) generated ids."""
        assert self.params is not None, "call load()/init_random() first"
        b, s = tokens.shape
        batch: Dict[str, Any] = {"tokens": tokens}
        if vision_embeds is not None:
            batch["vision_embeds"] = vision_embeds
            s = s + vision_embeds.shape[1]
        logits, caches = self.prefill_fn(self.params, batch)
        out = []
        pos = s
        for _ in range(max_new_tokens):
            if greedy:
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            else:
                rng, k = jax.random.split(rng)
                nxt = jax.random.categorical(k, logits).astype(jnp.int32)
            out.append(nxt)
            logits, caches = self.decode_fn(
                self.params, nxt[:, None], caches,
                jnp.full((b,), pos, jnp.int32))
            pos += 1
        return jnp.stack(out, axis=1)
