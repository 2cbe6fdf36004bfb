"""Causal GQA flash attention (prefill/train) as a Pallas TPU kernel.

TPU-native adaptation: q/k/v tiles are staged HBM->VMEM via BlockSpec, the
MXU consumes (block_q x d) @ (d x block_k) tiles, and the online-softmax
running statistics live in VMEM scratch that persists across the innermost
(sequential) kv grid dimension. GQA is handled by index-mapping kv blocks
with ``head // group`` so KV is never materialized per-q-head.

Oracle: ``ref.attention_ref``.
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
BLOCK_Q = 128   # default query tile; ``ops.attention`` pads prompts to it


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
               scale: float, causal: bool, block_q: int, block_k: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # With causal masking, kv blocks strictly above the diagonal contribute
    # nothing; skip their compute (they are still iterated by the grid).
    run = (not causal) or (ki * block_k <= qi * block_q + (block_q - 1))

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)          # (bq, d)
        k = k_ref[0].astype(jnp.float32)          # (bk, d)
        v = v_ref[0].astype(jnp.float32)          # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bk)
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        m_prev = m_scr[...]                        # (bq, 1)
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                     # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "scale", "block_q", "block_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, scale: Optional[float] = None,
                    block_q: int = BLOCK_Q, block_k: int = 128,
                    interpret: bool = False) -> jax.Array:
    """q: (b, sq, hq, d); k, v: (b, skv, hkv, d) -> (b, sq, hq, d)."""
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    if hq % hkv:
        raise ValueError(f"query heads {hq} are not a multiple of KV heads "
                         f"{hkv}")
    g = hq // hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    if sq % block_q or skv % block_k:
        raise ValueError(
            f"flash_attention tiles sq={sq} by block_q={block_q} and "
            f"skv={skv} by block_k={block_k}; pad the sequence to the block")

    # (b*hq, sq, d) rows; kv folded to (b*hkv, skv, d).
    qr = q.transpose(0, 2, 1, 3).reshape(b * hq, sq, d)
    kr = k.transpose(0, 2, 1, 3).reshape(b * hkv, skv, d)
    vr = v.transpose(0, 2, 1, 3).reshape(b * hkv, skv, d)

    def kv_index(h, qi, ki):
        # head-major layout: q row h = bi*hq + qh; kv row = bi*hkv + qh//g
        bi = h // hq
        qh = h % hq
        return (bi * hkv + qh // g, ki, 0)

    grid = (b * hq, sq // block_q, skv // block_k)
    out = pl.pallas_call(
        functools.partial(_fa_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda h, qi, ki: (h, qi, 0)),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_k, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda h, qi, ki: (h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(b, hq, sq, d).transpose(0, 2, 1, 3)
