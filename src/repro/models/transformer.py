"""Decoder stack builder.

Hybrid stacks (attn/mamba interleave, MoE alternation) are handled by
finding the smallest *block period* ``p`` such that the per-layer signature
``(mixer_kind, ffn_kind)`` repeats with period ``p``; parameters are stacked
over ``num_layers // p`` repeats and the stack runs as one ``lax.scan`` over
blocks of ``p`` explicitly-traced layers. This keeps compile time flat in
depth (one trace per distinct layer signature).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ATTN, ModelConfig
from repro.distributed.sharding import shard
from repro.models import attention as attn_mod
from repro.models import mamba2 as mamba_mod
from repro.models import moe as moe_mod
from repro.models.layers import (mlp_apply, mlp_init, mlp_specs,
                                 rmsnorm_apply, rmsnorm_init, rmsnorm_specs)

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Layer signatures and the block period.
# ---------------------------------------------------------------------------
def layer_signature(cfg: ModelConfig, i: int) -> Tuple[str, str]:
    kind = cfg.layer_kinds()[i]
    if cfg.is_moe_layer(i):
        ffn = "moe"
    elif cfg.d_ff:
        ffn = "dense"
    else:
        ffn = "none"
    return (kind, ffn)


def block_period(cfg: ModelConfig) -> int:
    sigs = [layer_signature(cfg, i) for i in range(cfg.num_layers)]
    for p in range(1, cfg.num_layers + 1):
        if cfg.num_layers % p:
            continue
        if all(sigs[i] == sigs[i % p] for i in range(cfg.num_layers)):
            return p
    return cfg.num_layers


# ---------------------------------------------------------------------------
# One layer.
# ---------------------------------------------------------------------------
def layer_init(rng, cfg: ModelConfig, i: int) -> Params:
    kind, ffn = layer_signature(cfg, i)
    k1, k2 = jax.random.split(rng)
    p: Params = {"norm1": rmsnorm_init(cfg.d_model)}
    if kind == ATTN:
        p["mixer"] = attn_mod.attn_init(k1, cfg)
    else:
        p["mixer"] = mamba_mod.mamba_init(k1, cfg)
    if ffn != "none":
        p["norm2"] = rmsnorm_init(cfg.d_model)
        p["ffn"] = (moe_mod.moe_init(k2, cfg) if ffn == "moe"
                    else mlp_init(k2, cfg.d_model, cfg.d_ff,
                                  jnp.dtype(cfg.dtype)))
    return p


def layer_specs(cfg: ModelConfig, i: int) -> Params:
    kind, ffn = layer_signature(cfg, i)
    p: Params = {"norm1": rmsnorm_specs()}
    p["mixer"] = (attn_mod.attn_specs(cfg) if kind == ATTN
                  else mamba_mod.mamba_specs(cfg))
    if ffn != "none":
        p["norm2"] = rmsnorm_specs()
        p["ffn"] = moe_mod.moe_specs(cfg) if ffn == "moe" else mlp_specs()
    return p


def _residual(cfg: ModelConfig, x: jax.Array, branch: jax.Array
              ) -> jax.Array:
    """``x`` plus the branch, scaled by ``cfg.residual_multiplier`` where
    the config sets it."""
    if cfg.residual_multiplier is not None:
        branch = branch * jnp.asarray(cfg.residual_multiplier, branch.dtype)
    return x + branch


def layer_apply(params: Params, cfg: ModelConfig, i_sig: Tuple[str, str],
                x: jax.Array, *, mode: str, cache: Optional[Params],
                pos, max_len: Optional[int] = None, layer=None, live=None
                ) -> Tuple[jax.Array, Optional[Params], jax.Array]:
    """In decode mode ``cache`` is stacked over the block repeats and
    ``layer`` is this layer's index into it; the stacked cache comes back
    with this layer's entry updated. ``live`` ((b,) bool, None for every
    slot) reaches the Mamba layers only."""
    kind, ffn = i_sig
    with jax.named_scope("norm"):
        h = rmsnorm_apply(params["norm1"], x, cfg.norm_eps)
    if kind == ATTN:
        mix, new_cache = attn_mod.attn_apply(
            params["mixer"], cfg, h, mode=mode, cache=cache, pos=pos,
            max_len=max_len, layer=layer)
    else:
        mix, new_cache = mamba_mod.mamba_apply(
            params["mixer"], cfg, h, mode=mode, cache=cache, layer=layer,
            live=live)
    x = _residual(cfg, x, mix)
    aux = jnp.zeros((), jnp.float32)
    if ffn != "none":
        with jax.named_scope("norm"):
            h = rmsnorm_apply(params["norm2"], x, cfg.norm_eps)
        with jax.named_scope("moe" if ffn == "moe" else "mlp"):
            if ffn == "moe":
                f, aux = moe_mod.moe_apply(params["ffn"], cfg, h, mode=mode)
            else:
                f = mlp_apply(params["ffn"], h)
        x = _residual(cfg, x, f)
    x = shard(x, ("batch", "seq", "embed_act"))
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# Cache construction (per layer position; stacked over blocks).
# ---------------------------------------------------------------------------
def init_layer_cache(cfg: ModelConfig, i: int, batch: int, max_len: int,
                     dtype) -> Optional[Params]:
    kind, _ = layer_signature(cfg, i)
    if kind == ATTN:
        return attn_mod.init_cache(cfg, batch, max_len, dtype)
    return mamba_mod.init_mamba_cache(cfg, batch, dtype)


def layer_cache_specs(cfg: ModelConfig, i: int) -> Optional[Params]:
    kind, _ = layer_signature(cfg, i)
    if kind == ATTN:
        return attn_mod.cache_specs()
    return mamba_mod.mamba_cache_specs()


# ---------------------------------------------------------------------------
# Stack: init + apply.
# ---------------------------------------------------------------------------
def stack_init(rng, cfg: ModelConfig) -> List[Params]:
    """Returns a list of per-position param trees, each stacked over the
    block repeats (leading dim num_layers // period)."""
    p = block_period(cfg)
    nb = cfg.num_layers // p
    rngs = jax.random.split(rng, cfg.num_layers)
    per_layer = [layer_init(rngs[i], cfg, i) for i in range(cfg.num_layers)]
    stacked = []
    for j in range(p):
        group = [per_layer[i] for i in range(j, cfg.num_layers, p)]
        stacked.append(jax.tree.map(lambda *xs: jnp.stack(xs), *group))
    return stacked


def stack_specs(cfg: ModelConfig) -> List[Params]:
    p = block_period(cfg)
    out = []
    for j in range(p):
        spec = layer_specs(cfg, j)
        out.append(jax.tree.map(
            lambda t: (None, *t), spec,
            is_leaf=lambda t: isinstance(t, tuple)))
    return out


def stack_caches(cfg: ModelConfig, batch: int, max_len: int, dtype
                 ) -> List[Optional[Params]]:
    p = block_period(cfg)
    nb = cfg.num_layers // p
    out = []
    for j in range(p):
        c = init_layer_cache(cfg, j, batch, max_len, dtype)
        out.append(jax.tree.map(
            lambda x: jnp.broadcast_to(x, (nb, *x.shape)), c))
    return out


def stack_cache_specs(cfg: ModelConfig) -> List[Optional[Params]]:
    p = block_period(cfg)
    out = []
    for j in range(p):
        spec = layer_cache_specs(cfg, j)
        out.append(jax.tree.map(
            lambda t: (None, *t), spec,
            is_leaf=lambda t: isinstance(t, tuple)))
    return out


def stack_apply(blocks: List[Params], cfg: ModelConfig, x: jax.Array, *,
                mode: str, caches: Optional[List[Params]] = None,
                pos=None, scan: bool = True, remat: str = "none",
                max_len: Optional[int] = None, live=None
                ) -> Tuple[jax.Array, Optional[List[Params]], jax.Array]:
    """Run all layers. Returns (x, new_caches, aux_loss_sum).

    Decode carries the stacked caches through the layers with the repeat
    index: each layer writes its new entry into them at that index, in
    place, and the same stacked buffers come back. Prefill returns each
    layer's cache as the scan's output, stacked."""
    p = block_period(cfg)
    nb = cfg.num_layers // p
    sigs = [layer_signature(cfg, j) for j in range(p)]
    decode = mode == "decode"

    def block_fn(x, block_params, block_caches, r):
        new_caches = []
        aux_total = jnp.zeros((), jnp.float32)
        for j in range(p):
            cache_j = None if block_caches is None else block_caches[j]
            x, nc, aux = layer_apply(
                block_params[j], cfg, sigs[j], x, mode=mode, cache=cache_j,
                pos=pos, max_len=max_len, layer=r, live=live)
            new_caches.append(nc)
            aux_total = aux_total + aux
        return x, new_caches, aux_total

    fn = block_fn
    if remat == "full":
        fn = jax.checkpoint(block_fn, static_argnums=())
    elif remat == "dots":
        fn = jax.checkpoint(
            block_fn,
            policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)

    zero = jnp.zeros((), jnp.float32)
    if scan and nb > 1:
        if decode:
            def body(carry, xs):
                x, aux, cs = carry
                bp, r = xs
                x, cs, a = fn(x, bp, cs, r)
                return (x, aux + a, cs), None

            (x, aux, caches), _ = jax.lax.scan(
                body, (x, zero, caches),
                (blocks, jnp.arange(nb, dtype=jnp.int32)))
            return x, caches, aux

        def body(carry, bp):
            x, aux = carry
            x, ncs, a = fn(x, bp, None, None)
            return (x, aux + a), ncs

        (x, aux), new_caches = jax.lax.scan(body, (x, zero), blocks)
        return x, (new_caches if mode == "prefill" else None), aux

    # Unrolled path: index the stacked leaves per repeat.
    aux = zero
    new_stack = [[] for _ in range(p)]
    for r in range(nb):
        bp = jax.tree.map(lambda a: a[r], blocks)
        x, ncs, a = fn(x, bp, caches, r)
        aux = aux + a
        if decode:
            caches = ncs
        else:
            for j, c in enumerate(ncs):
                new_stack[j].append(c)
    if decode:
        return x, caches, aux
    if mode == "prefill":
        return x, [jax.tree.map(lambda *xs: jnp.stack(xs), *new_stack[j])
                   for j in range(p)], aux
    return x, None, aux
