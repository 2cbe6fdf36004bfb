"""Mamba-2 SSD chunked scan as a Pallas TPU kernel.

Within a chunk of Q timesteps the SSD duality turns the recurrence into two
MXU matmuls (the (Q,Q) masked-decay "attention" and the inter-chunk state
read); the (p, n) running state lives in VMEM scratch and is carried across
the sequential chunk grid dimension — the TPU-native replacement for a
sequential scan over 500k steps.

    y_t = C_t . ( exp(L_t) h_in + sum_{j<=t} exp(L_t - L_j) dt_j B_j x_j )
    h_out = exp(L_last) h_in + sum_j exp(L_last - L_j) dt_j B_j x_j

with l_t = dt_t * A_h (A_h < 0), L = inclusive prefix sum of l, taken as a
product with a lower-triangular mask (Mosaic has no cumsum).

Oracle: ``ref.ssd_ref`` (sequential recurrence).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# the kernel's name in the compiled program and the device trace
KERNEL_NAME = "ssd_scan"


def _ssd_kernel(a_coef_ref, x_ref, dt_ref, dt_row_ref, b_ref, c_ref, y_ref,
                state_ref, state_scr, *, chunk: int):
    h = pl.program_id(0)
    ci = pl.program_id(1)
    nc = pl.num_programs(1)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    a = a_coef_ref[h]                                   # A_h (negative)
    x = x_ref[0].astype(jnp.float32)                    # (Q, p)
    dt = dt_ref[0].astype(jnp.float32)                  # (Q, 1)
    dt_row = dt_row_ref[0].astype(jnp.float32)          # (1, Q)
    B = b_ref[0].astype(jnp.float32)                    # (Q, n)
    C = c_ref[0].astype(jnp.float32)                    # (Q, n)

    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = rows >= cols
    tri = jnp.where(causal, 1.0, 0.0)                   # (Q, Q) lower
    # inclusive prefix sums L_t = sum_{j<=t} l_j as products with the
    # triangular mask, once as a column and once as a row; in full f32,
    # since exp(L_t - L_j) magnifies any rounding of L
    exact = jax.lax.Precision.HIGHEST
    L = jax.lax.dot_general(tri, dt * a, (((1,), (0,)), ((), ())),
                            precision=exact,
                            preferred_element_type=jnp.float32)   # (Q, 1)
    L_row = jax.lax.dot_general(dt_row * a, tri, (((1,), (1,)), ((), ())),
                                precision=exact,
                                preferred_element_type=jnp.float32)  # (1, Q)
    # intra-chunk: M[t, j] = (C_t . B_j) exp(L_t - L_j) [j <= t]
    cb = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Q, Q)
    M = cb * jnp.exp(jnp.where(causal, L - L_row, NEG_INF))
    y = jax.lax.dot_general(M, x * dt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (Q, p)
    # inter-chunk: y += exp(L_t) * (C_t . h_in);  state is (n, p)
    y += jnp.exp(L) * jax.lax.dot_general(
        C, state_scr[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    y_ref[0] = y.astype(y_ref.dtype)
    # state update
    total = jnp.sum(dt * a, axis=0, keepdims=True)      # (1, 1) = L_last
    w = jnp.exp(total - L) * dt                         # (Q, 1)
    state_scr[...] = jnp.exp(total) * state_scr[...] + jax.lax.dot_general(
        B * w, x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)             # (n, p)

    @pl.when(ci == nc - 1)
    def _emit_state():
        state_ref[0] = state_scr[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
             C: jax.Array, D: jax.Array, *, chunk: int = 128,
             interpret: bool = False):
    """x: (b, s, h, p); dt: (b, s, h); A,D: (h,); B,C: (b, s, n).

    Returns (y: (b, s, h, p), final_state: (b, h, n, p))  [fp32 state].
    """
    b, s, h, p = x.shape
    n = B.shape[-1]
    chunk = min(chunk, s)
    if s % chunk:
        raise ValueError(f"ssd_scan tiles s={s} by chunk={chunk}")
    nc = s // chunk

    xr = x.transpose(0, 2, 1, 3).reshape(b * h, s, p)
    dt_cols = dt.transpose(0, 2, 1).reshape(b * h, s, 1)
    dt_rows = dt.transpose(0, 2, 1).reshape(b * h, 1, s)
    a_coef = jnp.tile(A.astype(jnp.float32), b)         # (b*h,)

    def bc_index(bh, ci, a_ref):
        return (bh // h, ci, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * h, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, p), lambda bh, ci, a: (bh, ci, 0)),
            pl.BlockSpec((1, chunk, 1), lambda bh, ci, a: (bh, ci, 0)),
            pl.BlockSpec((1, 1, chunk), lambda bh, ci, a: (bh, 0, ci)),
            pl.BlockSpec((1, chunk, n), bc_index),
            pl.BlockSpec((1, chunk, n), bc_index),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, p), lambda bh, ci, a: (bh, ci, 0)),
            pl.BlockSpec((1, n, p), lambda bh, ci, a: (bh, 0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
    )
    y, state = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk),
        grid_spec=grid_spec,
        name=KERNEL_NAME,
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s, p), x.dtype),
            jax.ShapeDtypeStruct((b * h, n, p), jnp.float32),
        ],
        interpret=interpret,
    )(a_coef, xr, dt_cols, dt_rows, B, C)
    y = y.reshape(b, h, s, p).transpose(0, 2, 1, 3)
    y = y + x.astype(jnp.float32).astype(x.dtype) * D.astype(x.dtype)[None, None, :, None]
    state = state.reshape(b, h, n, p).transpose(0, 1, 3, 2)  # (b, h, p, n)
    return y, state
