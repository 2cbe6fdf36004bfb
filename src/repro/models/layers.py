"""Shared layers: initializers, RMSNorm, RoPE, SwiGLU MLP, embeddings.

Functional style: every module is an ``init(rng, ...) -> params`` plus an
``apply(params, x, ...)``, with a parallel ``specs(...)`` returning the
logical sharding names for each param leaf (consumed by
``distributed.sharding``).
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import shard
from repro.kernels import ops

Params = Dict[str, Any]


def dense_init(rng, shape, dtype, scale: float = 0.02) -> jax.Array:
    return (jax.random.normal(rng, shape, jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------
def rmsnorm_init(d: int) -> Params:
    return {"scale": jnp.ones((d,), jnp.float32)}


def rmsnorm_specs() -> Params:
    return {"scale": (None,)}


def rmsnorm_apply(params: Params, x: jax.Array, eps: float) -> jax.Array:
    return ops.rmsnorm(x, params["scale"], eps)


# ---------------------------------------------------------------------------
# Rotary position embeddings.
# ---------------------------------------------------------------------------
def rope_table(positions: jax.Array, head_dim: int, theta: float
               ) -> Tuple[jax.Array, jax.Array]:
    """positions: (s,) int -> (sin, cos) each (s, head_dim//2) fp32."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    return jnp.sin(ang), jnp.cos(ang)


def rope_apply(x: jax.Array, sin: jax.Array, cos: jax.Array) -> jax.Array:
    """x: (b, s, h, d); sin/cos: (s, d//2) or per-batch (b, s, d//2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    if sin.ndim == 2:
        sin = sin[None, :, None, :]
        cos = cos[None, :, None, :]
    else:
        sin = sin[:, :, None, :]
        cos = cos[:, :, None, :]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1
    ).astype(x.dtype)


# ---------------------------------------------------------------------------
# SwiGLU MLP (dense FFN).
# ---------------------------------------------------------------------------
def mlp_init(rng, d: int, f: int, dtype) -> Params:
    k1, k2, k3 = jax.random.split(rng, 3)
    return {
        "w_gate": dense_init(k1, (d, f), dtype),
        "w_up": dense_init(k2, (d, f), dtype),
        "w_down": dense_init(k3, (f, d), dtype),
    }


def mlp_specs() -> Params:
    return {
        "w_gate": ("p_embed", "p_mlp"),
        "w_up": ("p_embed", "p_mlp"),
        "w_down": ("p_mlp", "p_embed"),
    }


def mlp_apply(params: Params, x: jax.Array) -> jax.Array:
    g = jnp.einsum("bsd,df->bsf", x, params["w_gate"])
    u = jnp.einsum("bsd,df->bsf", x, params["w_up"])
    h = (jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype)) * u
    h = shard(h, ("batch", "seq", "mlp_act"))
    return jnp.einsum("bsf,fd->bsd", h, params["w_down"])


# ---------------------------------------------------------------------------
# Token embedding / unembedding.
# ---------------------------------------------------------------------------
def embed_init(rng, vocab: int, d: int, dtype, tie: bool,
               scale: float = 0.02) -> Params:
    k1, k2 = jax.random.split(rng)
    p = {"embedding": dense_init(k1, (vocab, d), dtype, scale)}
    if not tie:
        p["unembed"] = dense_init(k2, (d, vocab), dtype)
    return p


def embed_specs(tie: bool) -> Params:
    p = {"embedding": ("p_vocab", "p_embed")}
    if not tie:
        p["unembed"] = ("p_embed", "p_vocab")
    return p


def embed_apply(params: Params, tokens: jax.Array) -> jax.Array:
    x = jnp.take(params["embedding"], tokens, axis=0)
    return shard(x, ("batch", "seq", "embed_act"))


def unembed_apply(params: Params, x: jax.Array) -> jax.Array:
    if "unembed" in params:
        logits = jnp.einsum("bsd,dv->bsv", x, params["unembed"])
    else:
        logits = jnp.einsum("bsd,vd->bsv", x, params["embedding"])
    return shard(logits, ("batch", "seq", "vocab_act"))
