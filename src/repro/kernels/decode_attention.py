"""Flash-decode attention: one query token vs a long KV cache, as a Pallas
TPU kernel with per-batch valid-length masking.

The kv axis is the innermost (sequential) grid dimension; online-softmax
stats persist in VMEM scratch. Valid lengths arrive via scalar prefetch
(SMEM) so block masking is computed before the VMEM tiles are touched.

Oracle: ``ref.decode_attention_ref``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
BLOCK_K = 256   # default KV tile; ``ops.cache_len`` sizes caches to it


def _dec_kernel(length_ref, q_ref, k_ref, v_ref, o_ref,
                m_scr, l_scr, acc_scr, *,
                scale: float, block_k: int, hq: int, g: int):
    h = pl.program_id(0)
    ki = pl.program_id(1)
    nk = pl.num_programs(1)
    bi = h // hq

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = length_ref[bi]
    # Skip fully-invalid blocks.
    @pl.when(ki * block_k < length)
    def _compute():
        q = q_ref[0].astype(jnp.float32)            # (1, d)
        k = k_ref[0].astype(jnp.float32)            # (bk, d)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale    # (1, bk)
        pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
        s = jnp.where(pos < length, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "block_k", "interpret"))
def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     length: jax.Array, *, scale: Optional[float] = None,
                     block_k: int = BLOCK_K, interpret: bool = False
                     ) -> jax.Array:
    """q: (b, hq, d); k, v: (b, skv, hkv, d); length: (b,) -> (b, hq, d)."""
    b, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    g = hq // hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    block_k = min(block_k, skv)
    if skv % block_k:
        raise ValueError(
            f"decode_attention tiles the cache length {skv} by "
            f"block_k={block_k}; size the cache with ops.cache_len")

    qr = q.reshape(b * hq, 1, d)
    kr = k.transpose(0, 2, 1, 3).reshape(b * hkv, skv, d)
    vr = v.transpose(0, 2, 1, 3).reshape(b * hkv, skv, d)

    def kv_index(h, ki, length):  # scalar-prefetch ref comes last
        bi = h // hq
        qh = h % hq
        return (bi * hkv + qh // g, ki, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * hq, skv // block_k),
        in_specs=[
            pl.BlockSpec((1, 1, d), lambda h, ki, length: (h, 0, 0)),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_k, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, 1, d), lambda h, ki, length: (h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_dec_kernel, scale=scale, block_k=block_k,
                          hq=hq, g=g),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * hq, 1, d), q.dtype),
        interpret=interpret,
    )(length.astype(jnp.int32), qr, kr, vr)
    return out.reshape(b, hq, d)
