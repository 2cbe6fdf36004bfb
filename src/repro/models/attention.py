"""GQA multi-head attention with RoPE (or no positions) and a decode KV
cache.

Modes:
  * train   — full causal self-attention (no cache)
  * prefill — causal self-attention that also emits the KV cache laid out
              in the decode sharding (``kv_seq`` sequence-sharded)
  * decode  — one new token a slot, written at that slot's position in
              ``pos`` (shape ``(b,)``) into the cache stacked over
              layers, at the layer's index, and attended against in place
              (flash-decode partial-softmax combine under GSPMD)

The KV cache is head-major, ``(b, hkv, L, d)`` a layer and
``(layers, b, hkv, L, d)`` stacked, the layout the decode kernel reads.

With ``cfg.rope`` off (NoPE) no rotary embedding is applied in any mode;
``cfg.attention_multiplier``, where set, scales the scores in place of
``1/sqrt(head_dim)``.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.distributed.sharding import shard
from repro.kernels import ops
from repro.models.layers import dense_init, rope_apply, rope_table

Params = Dict[str, Any]


def attn_init(rng, cfg: ModelConfig) -> Params:
    d, hq, hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    dtype = jnp.dtype(cfg.dtype)
    ks = jax.random.split(rng, 4)
    p: Params = {
        "wq": dense_init(ks[0], (d, hq, hd), dtype),
        "wk": dense_init(ks[1], (d, hkv, hd), dtype),
        "wv": dense_init(ks[2], (d, hkv, hd), dtype),
        "wo": dense_init(ks[3], (hq, hd, d), dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((hq, hd), dtype)
        p["bk"] = jnp.zeros((hkv, hd), dtype)
        p["bv"] = jnp.zeros((hkv, hd), dtype)
    return p


def attn_specs(cfg: ModelConfig) -> Params:
    p: Params = {
        "wq": ("p_embed", "p_heads", "p_head_dim"),
        "wk": ("p_embed", "p_kv_heads", "p_head_dim"),
        "wv": ("p_embed", "p_kv_heads", "p_head_dim"),
        "wo": ("p_heads", "p_head_dim", "p_embed"),
    }
    if cfg.qkv_bias:
        p["bq"] = ("p_heads", "p_head_dim")
        p["bk"] = ("p_kv_heads", "p_head_dim")
        p["bv"] = ("p_kv_heads", "p_head_dim")
    return p


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype) -> Params:
    hkv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    return {
        "k": jnp.zeros((batch, hkv, max_len, hd), dtype),
        "v": jnp.zeros((batch, hkv, max_len, hd), dtype),
    }


def cache_specs() -> Params:
    return {
        "k": ("batch", "kv_heads_act", "kv_seq", "head_dim_act"),
        "v": ("batch", "kv_heads_act", "kv_seq", "head_dim_act"),
    }


def _project_qkv(params: Params, cfg: ModelConfig, x: jax.Array):
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    if cfg.qkv_bias:
        q = q + params["bq"][None, None]
        k = k + params["bk"][None, None]
        v = v + params["bv"][None, None]
    q = shard(q, ("batch", "seq", "heads_act", None))
    k = shard(k, ("batch", "seq", "kv_heads_act", None))
    v = shard(v, ("batch", "seq", "kv_heads_act", None))
    return q, k, v


@jax.named_scope("attn")
def attn_apply(params: Params, cfg: ModelConfig, x: jax.Array, *,
               mode: str, cache: Optional[Params] = None,
               pos: Optional[jax.Array] = None,
               max_len: Optional[int] = None, layer=None
               ) -> Tuple[jax.Array, Optional[Params]]:
    """x: (b, s, d). Returns (out, new_cache). In decode, ``cache`` is
    stacked over layers and ``layer`` indexes it: the new position is
    written at that index in place and the stacked cache is returned.
    Named scopes: ``attn`` around it all, ``kv_write`` around the cache
    writes and ``attn_core`` around the attention kernel call."""
    b, s, d = x.shape
    if mode in ("train", "prefill"):
        if cfg.rope:
            sin, cos = rope_table(jnp.arange(s), cfg.resolved_head_dim,
                                  cfg.rope_theta)
        q, k, v = _project_qkv(params, cfg, x)
        if cfg.rope:
            q = rope_apply(q, sin, cos)
            k = rope_apply(k, sin, cos)
        with jax.named_scope("attn_core"):
            out = ops.attention(q, k, v, causal=True,
                                scale=cfg.attention_multiplier)
        new_cache = None
        if mode == "prefill":
            with jax.named_scope("kv_write"):
                kc, vc = (t.transpose(0, 2, 1, 3) for t in (k, v))
                if max_len is not None and max_len > s:
                    pad = ((0, 0), (0, 0), (0, max_len - s), (0, 0))
                    kc, vc = jnp.pad(kc, pad), jnp.pad(vc, pad)
                spec = ("batch", "kv_heads_act", "kv_seq", None)
                new_cache = {"k": shard(kc, spec), "v": shard(vc, spec)}
    else:  # decode
        assert cache is not None and pos is not None and layer is not None
        pos = jnp.asarray(pos, jnp.int32)                   # (b,)
        q, k, v = _project_qkv(params, cfg, x)              # s == 1
        cdt = cache["k"].dtype
        layer = jnp.asarray(layer, jnp.int32)
        if cfg.rope:
            # Per-slot RoPE phases (continuous batching: every slot is at
            # its own sequence position).
            sin, cos = rope_table(pos, cfg.resolved_head_dim,
                                  cfg.rope_theta)           # (b, d/2)
            sin, cos = sin[:, None], cos[:, None]           # (b, 1, d/2)
            q = rope_apply(q, sin, cos)
            k = rope_apply(k, sin, cos)
        # one row of d per (slot, head): a write of whole (hkv, d)
        # windows would have XLA lay the cache out head-minor
        bidx = jnp.arange(b)[:, None]
        hidx = jnp.arange(k.shape[2])[None, :]
        at = (layer, bidx, hidx, pos[:, None])
        with jax.named_scope("kv_write"):
            k_all = cache["k"].at[at].set(k[:, 0].astype(cdt))
            v_all = cache["v"].at[at].set(v[:, 0].astype(cdt))
        length = pos + 1
        stacked = (None, "batch", "kv_heads_act", "kv_seq", None)
        k_all, v_all = shard(k_all, stacked), shard(v_all, stacked)
        with jax.named_scope("attn_core"):
            out1 = ops.decode_attention(q[:, 0], k_all, v_all, length, layer,
                                        scale=cfg.attention_multiplier)
        out = out1[:, None]
        new_cache = {"k": k_all, "v": v_all}
    out = shard(out, ("batch", "seq", "heads_act", None))
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    return y, new_cache
