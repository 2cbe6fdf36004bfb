"""W8A8 int8 matmul with per-row/per-column scales, as a Pallas TPU kernel.

This is the TPU-native analogue of the paper's INT8-on-Hexagon-DSP serving
path (its most energy-efficient configuration): int8 x int8 -> int32 MXU
accumulation, dequantized once in the epilogue with per-channel scales.

Oracle: ``ref.int8_matmul_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mm_kernel(x_ref, w_ref, sx_ref, sw_ref, o_ref, acc_scr):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    acc_scr[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(ki == nk - 1)
    def _finalize():
        sx = sx_ref[...].astype(jnp.float32)       # (bm, 1)
        sw = sw_ref[...].astype(jnp.float32)       # (1, bn)
        o_ref[...] = (acc_scr[...].astype(jnp.float32) * sx * sw
                      ).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "out_dtype",
                     "interpret"))
def int8_matmul(x_q: jax.Array, sx: jax.Array, w_q: jax.Array,
                sw: jax.Array, *, block_m: int = 128, block_n: int = 128,
                block_k: int = 512, out_dtype=jnp.float32,
                interpret: bool = False) -> jax.Array:
    """x_q: (m, k) int8; sx: (m,); w_q: (k, n) int8; sw: (n,) -> (m, n)."""
    m, k = x_q.shape
    k2, n = w_q.shape
    if k != k2:
        raise ValueError(f"int8_matmul: x is (m={m}, k={k}), w is "
                         f"(k={k2}, n={n})")
    block_m = min(block_m, m)
    block_n = min(block_n, n)
    block_k = min(block_k, k)
    if m % block_m or n % block_n or k % block_k:
        raise ValueError(
            f"int8_matmul tiles (m, n, k)=({m}, {n}, {k}) by blocks "
            f"({block_m}, {block_n}, {block_k})")

    grid = (m // block_m, n // block_n, k // block_k)
    return pl.pallas_call(
        _mm_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda mi, ni, ki: (mi, ki)),
            pl.BlockSpec((block_k, block_n), lambda mi, ni, ki: (ki, ni)),
            pl.BlockSpec((block_m, 1), lambda mi, ni, ki: (mi, 0)),
            pl.BlockSpec((1, block_n), lambda mi, ni, ki: (0, ni)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda mi, ni, ki: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)],
        interpret=interpret,
    )(x_q, w_q, sx.reshape(m, 1), sw.reshape(1, n))
