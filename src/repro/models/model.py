"""The LM wrapper: init / specs / forward / loss / prefill / decode.

``batch`` dict convention (produced by the data pipeline / input_specs):
  tokens : (b, s) int32
  labels : (b, s) int32       (next-token targets, already aligned)
  mask   : (b, s) float32     (1 where the loss counts)
  vision_embeds : (b, ft, d)  (optional; VLM/audio frontend stubs)
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.distributed.sharding import shard
from repro.models import transformer as stack
from repro.models.layers import (embed_apply, embed_init, embed_specs,
                                 rmsnorm_apply, rmsnorm_init, rmsnorm_specs,
                                 unembed_apply)

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Params.
# ---------------------------------------------------------------------------
def init_params(cfg: ModelConfig, rng: jax.Array) -> Params:
    """Random weights (``N(0, 0.02)`` matrices). Where the config sets an
    embedding multiplier the embedding is drawn at ``0.02 / multiplier``,
    so that the residual stream starts at the usual spread: a random tied
    model whose embeddings came in twelve times larger would only repeat
    its last token."""
    k_embed, k_stack = jax.random.split(rng)
    scale = 0.02 / (cfg.embedding_multiplier or 1.0)
    return {
        "embed": embed_init(k_embed, cfg.vocab_size, cfg.d_model,
                            jnp.dtype(cfg.dtype), cfg.tie_embeddings,
                            scale),
        "blocks": stack.stack_init(k_stack, cfg),
        "final_norm": rmsnorm_init(cfg.d_model),
    }


def param_specs(cfg: ModelConfig) -> Params:
    return {
        "embed": embed_specs(cfg.tie_embeddings),
        "blocks": stack.stack_specs(cfg),
        "final_norm": rmsnorm_specs(),
    }


def param_shapes(cfg: ModelConfig) -> Params:
    """Abstract (ShapeDtypeStruct) params without allocation."""
    return jax.eval_shape(
        lambda: init_params(cfg, jax.random.key(0)))


# ---------------------------------------------------------------------------
# Forward passes.
# ---------------------------------------------------------------------------
def _embed_inputs(params: Params, cfg: ModelConfig, batch: Dict[str, Any]
                  ) -> jax.Array:
    with jax.named_scope("embed"):
        x = embed_apply(params["embed"], batch["tokens"])
        if cfg.embedding_multiplier is not None:
            x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
        if "vision_embeds" in batch and batch["vision_embeds"] is not None:
            ve = batch["vision_embeds"].astype(x.dtype)
            x = jnp.concatenate([ve, x], axis=1)
            x = shard(x, ("batch", "seq", "embed_act"))
    return x


def forward(params: Params, cfg: ModelConfig, batch: Dict[str, Any], *,
            mode: str = "train", caches: Optional[List[Params]] = None,
            pos=None, scan: bool = True, remat: str = "none",
            max_len: Optional[int] = None, live=None
            ) -> Tuple[jax.Array, Optional[List[Params]], jax.Array]:
    """Returns (logits, new_caches, aux_loss)."""
    x = _embed_inputs(params, cfg, batch)
    x, new_caches, aux = stack.stack_apply(
        params["blocks"], cfg, x, mode=mode, caches=caches, pos=pos,
        scan=scan, remat=remat, max_len=max_len, live=live)
    with jax.named_scope("norm"):
        x = rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    with jax.named_scope("lm_head"):
        logits = unembed_apply(params["embed"] if cfg.tie_embeddings
                               else {**params["embed"]}, x)
        if cfg.logits_scaling is not None:
            logits = logits / jnp.asarray(cfg.logits_scaling, logits.dtype)
    return logits, new_caches, aux


def _ce_terms(logits_f32: jax.Array, labels: jax.Array, mask: jax.Array):
    lse = jax.scipy.special.logsumexp(logits_f32, axis=-1)
    picked = jnp.take_along_axis(logits_f32, labels[..., None],
                                 axis=-1)[..., 0]
    nll = (lse - picked) * mask
    return jnp.sum(nll), jnp.sum((lse * mask) ** 2)


def loss_fn(params: Params, cfg: ModelConfig, batch: Dict[str, Any], *,
            scan: bool = True, remat: str = "none",
            z_loss: float = 1e-4, loss_chunk: int = 0
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    labels = batch["labels"]
    mask = batch["mask"].astype(jnp.float32)
    denom = jnp.maximum(jnp.sum(mask), 1.0)

    if loss_chunk:
        # Chunked CE: run the trunk once, then compute logits + logsumexp
        # per sequence chunk under remat so the (b, s, vocab) fp32 logits
        # tensor never materializes (beyond-paper memory lever; decisive
        # for vocab-202k llama4).
        x = _embed_inputs(params, cfg, batch)
        x, _, aux = stack.stack_apply(
            params["blocks"], cfg, x, mode="train", scan=scan, remat=remat)
        x = rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
        ft = x.shape[1] - labels.shape[1]
        if ft:
            x = x[:, ft:]
        s = labels.shape[1]
        chunk = min(loss_chunk, s)
        pad = (-s) % chunk
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
            labels = jnp.pad(labels, ((0, 0), (0, pad)))
            mask = jnp.pad(mask, ((0, 0), (0, pad)))
        nc = x.shape[1] // chunk
        xs = x.reshape(x.shape[0], nc, chunk, -1).swapaxes(0, 1)
        ls = labels.reshape(labels.shape[0], nc, chunk).swapaxes(0, 1)
        ms = mask.reshape(mask.shape[0], nc, chunk).swapaxes(0, 1)

        @jax.checkpoint
        def chunk_ce(args):
            xc, lc, mc = args
            logits = unembed_apply(params["embed"], xc).astype(jnp.float32)
            if cfg.logits_scaling is not None:
                logits = logits / cfg.logits_scaling
            return _ce_terms(logits, lc, mc)

        def body(carry, args):
            nll_c, z_c = chunk_ce(args)
            return (carry[0] + nll_c, carry[1] + z_c), None

        (nll_sum, z_sum), _ = jax.lax.scan(
            body, (jnp.zeros(()), jnp.zeros(())), (xs, ls, ms))
        ce = nll_sum / denom
        zl = z_loss * z_sum / denom
    else:
        logits, _, aux = forward(params, cfg, batch, mode="train",
                                 scan=scan, remat=remat)
        if logits.shape[1] != labels.shape[1]:
            # Frontend stub prepends embeddings; score text positions only.
            ft = logits.shape[1] - labels.shape[1]
            logits = logits[:, ft:]
        nll_sum, z_sum = _ce_terms(logits.astype(jnp.float32), labels, mask)
        ce = nll_sum / denom
        zl = z_loss * z_sum / denom
    total = ce + aux + zl
    return total, {"ce": ce, "aux": aux, "z_loss": zl,
                   "tokens": jnp.sum(mask)}


# ---------------------------------------------------------------------------
# Serving entry points.
# ---------------------------------------------------------------------------
def prefill(params: Params, cfg: ModelConfig, batch: Dict[str, Any], *,
            scan: bool = True, max_len: Optional[int] = None
            ) -> Tuple[jax.Array, List[Params]]:
    """Returns (last-position logits, caches padded to max_len)."""
    logits, caches, _ = forward(params, cfg, batch, mode="prefill",
                                scan=scan, max_len=max_len)
    return logits[:, -1], caches


def decode_step(params: Params, cfg: ModelConfig, tokens: jax.Array,
                caches: List[Params], pos, *, scan: bool = True, live=None
                ) -> Tuple[jax.Array, List[Params]]:
    """tokens: (b, 1); pos: (b,) int32; live: (b,) bool, the slots still
    decoding (None for all). The Pallas SSD update leaves a finished
    slot's state as it was; its logits are not to be read. Returns
    (logits (b, vocab), new caches)."""
    logits, new_caches, _ = forward(
        params, cfg, {"tokens": tokens}, mode="decode", caches=caches,
        pos=pos, scan=scan, live=live)
    return logits[:, 0], new_caches


# ---------------------------------------------------------------------------
# Cache helpers.
# ---------------------------------------------------------------------------
def init_caches(cfg: ModelConfig, batch: int, max_len: int,
                dtype=None) -> List[Params]:
    dtype = dtype or jnp.dtype(cfg.dtype)
    return stack.stack_caches(cfg, batch, max_len, dtype)


def cache_specs(cfg: ModelConfig) -> List[Params]:
    return stack.stack_cache_specs(cfg)
