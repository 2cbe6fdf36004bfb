"""Flash-decode attention: one query token vs a long KV cache, as a Pallas
TPU kernel with per-slot valid-length masking.

The cache is head-major, ``(b, hkv, L, d)``, and may come stacked over
layers, ``(layers, b, hkv, L, d)``: the kernel reads the stacked buffer
where it lies, at the layer given by scalar prefetch, so no per-layer
slice or transpose is made before it.

The grid is ``(b, L / block_k)``: one program per slot and KV block, each
covering all ``hkv`` heads of its slot with q as an ``(hkv, g, d)`` tile,
so every KV block is fetched once, not once per query head. The kv axis
is the innermost (sequential) grid dimension; online-softmax stats
persist in VMEM scratch. Valid lengths arrive via scalar prefetch (SMEM):
the K and V index maps stop at each slot's last live block, so blocks
past the length repeat its index and Pallas fetches nothing for them,
and their compute is skipped.

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
# the kernel's name in the compiled program and the device trace
KERNEL_NAME = "decode_attention"


def kv_block_index(bi, ki, length_ref, layer_ref, *, block_k: int):
    """Block index of K and V for slot ``bi``'s program ``ki``: the
    cache's own block up to the slot's last live one, then that one."""
    last = jnp.maximum((length_ref[bi] + block_k - 1) // block_k - 1, 0)
    return (layer_ref[0], bi, 0, jnp.minimum(ki, last), 0)


def _dec_kernel(length_ref, layer_ref, q_ref, k_ref, v_ref, o_ref,
                m_scr, l_scr, acc_scr, *, scale: float, block_k: int):
    bi = pl.program_id(0)
    ki = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = length_ref[bi]

    # Skip fully-invalid blocks.
    @pl.when(ki * block_k < length)
    def _compute():
        q = q_ref[0]                                 # (hkv, g, d)
        k = k_ref[0, 0]                              # (hkv, bk, d)
        v = v_ref[0, 0].astype(jnp.float32)
        # QK in the operands' own dtype: a product of two bf16 values is
        # exact in the f32 accumulator.
        cdt = jnp.promote_types(q.dtype, k.dtype)
        s = jax.lax.dot_general(
            q.astype(cdt), k.astype(cdt), (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale    # (hkv, g, bk)
        pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(pos < length, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)      # (hkv, g, d)
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "block_k", "interpret"))
def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     length: jax.Array, layer=None, *,
                     scale: Optional[float] = None, block_k: int = BLOCK_K,
                     interpret: bool = False) -> jax.Array:
    """q: (b, hq, d); k, v: (layers, b, hkv, L, d) with ``layer`` picking
    one, or (b, hkv, L, d); length: (b,) -> (b, hq, d)."""
    if k.ndim == 4:
        k, v, layer = k[None], v[None], 0
    b, hq, d = q.shape
    _, _, hkv, skv, _ = k.shape
    g = hq // hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    block_k = min(block_k, skv)
    if skv % block_k:
        raise ValueError(
            f"decode_attention tiles the cache length {skv} by "
            f"block_k={block_k}; size the cache with ops.cache_len")

    kv_spec = pl.BlockSpec((1, 1, hkv, block_k, d),
                           functools.partial(kv_block_index, block_k=block_k))
    q_spec = pl.BlockSpec((1, hkv, g, d), lambda bi, ki, *_: (bi, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, skv // block_k),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((hkv, g, 1), jnp.float32),
            pltpu.VMEM((hkv, g, 1), jnp.float32),
            pltpu.VMEM((hkv, g, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_dec_kernel, scale=scale, block_k=block_k),
        grid_spec=grid_spec,
        name=KERNEL_NAME,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        interpret=interpret,
    )(length.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      q.reshape(b, hkv, g, d), k, v)
    return out.reshape(b, hq, d)
