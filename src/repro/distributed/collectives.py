"""Overlapped collectives: ring collective-matmul under ``shard_map``.

This is the TPU-native implementation of the paper's §5.3 insight
("transfer computation-required data first" to pipeline communication with
computation): instead of `all_gather(x) @ w` (a blocking transfer followed
by compute), the gathered operand circulates around the ring one shard-chunk
per step via ``lax.ppermute`` while the MXU consumes the chunk already in
hand. Peak comm/compute overlap is ~(A-1)/A of the transfer.

All functions run *inside* ``shard_map`` (they use named axes).
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map


def _axis_size(axis_name: str) -> int:
    return jax.lax.psum(1, axis_name)


def _pvary(x: jax.Array, axis_name: str) -> jax.Array:
    """Mark a replicated value as device-varying over `axis_name` (required
    for carries that mix with ppermute'd values under shard_map's vma type
    system)."""
    return jax.lax.pcast(x, (axis_name,), to="varying")


def _ring_perm(a: int) -> Sequence[tuple]:
    # send j -> j-1: after i hops we hold the chunk originally at (idx+i)%A
    return [(j, (j - 1) % a) for j in range(a)]


# ---------------------------------------------------------------------------
# All-gather matmul:  y = all_gather(x, axis) @ w_local
#   x_local : (m, k_l)      -- sharded on k (the contracting dim)
#   w_local : (A*k_l, n_l)  -- full contracting dim, n sharded
# Returns y_local: (m, n_l).
# ---------------------------------------------------------------------------
def ring_ag_matmul(x_local: jax.Array, w_local: jax.Array,
                   axis_name: str) -> jax.Array:
    a = _axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    m, kl = x_local.shape
    n_l = w_local.shape[1]
    perm = _ring_perm(a)

    def body(i, carry):
        acc, chunk = carry
        src = (idx + i) % a
        w_rows = jax.lax.dynamic_slice_in_dim(w_local, src * kl, kl, axis=0)
        acc = acc + jnp.dot(chunk, w_rows,
                            preferred_element_type=jnp.float32)
        # Send the chunk onward while (conceptually) the next matmul runs.
        chunk = jax.lax.ppermute(chunk, axis_name, perm)
        return acc, chunk

    acc0 = _pvary(jnp.zeros((m, n_l), jnp.float32), axis_name)
    acc, _ = jax.lax.fori_loop(0, a, body, (acc0, x_local))
    return acc.astype(x_local.dtype)


# ---------------------------------------------------------------------------
# Matmul reduce-scatter:  y = reduce_scatter(x @ w, axis, scatter dim=1)
#   x_local : (m, k_l)      -- k sharded (partial contributions)
#   w_local : (k_l, n)      -- full n
# Returns y_local: (m, n / A): the n-shard owned by this device, fully
# reduced. Partial products for the chunk that is `i` hops away are computed
# while the accumulator ring-hops toward its owner.
# ---------------------------------------------------------------------------
def ring_matmul_rs(x_local: jax.Array, w_local: jax.Array,
                   axis_name: str) -> jax.Array:
    a = _axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    m, kl = x_local.shape
    n = w_local.shape[1]
    assert n % a == 0
    nl = n // a
    perm = _ring_perm(a)

    def partial(i):
        # partial(j) contributes to the accumulator that is j ring-hops away
        # from its final owner; with a j->j-1 ring that owner is idx - j.
        tgt = (idx - i) % a
        w_cols = jax.lax.dynamic_slice_in_dim(w_local, tgt * nl, nl, axis=1)
        return jnp.dot(x_local, w_cols, preferred_element_type=jnp.float32)

    def body(i, acc):
        acc = jax.lax.ppermute(acc, axis_name, perm)
        step = a - 1 - i  # chunks farthest from their owner go first
        return acc + partial(step)

    acc = partial(a - 1)
    acc = jax.lax.fori_loop(1, a, lambda i, c: body(i, c), acc)
    return acc.astype(x_local.dtype)


# ---------------------------------------------------------------------------
# Baseline (unoverlapped) variants — the paper-faithful §5.3 "tensor
# parallelism without pipelining" reference points.
# ---------------------------------------------------------------------------
def naive_ag_matmul(x_local: jax.Array, w_local: jax.Array,
                    axis_name: str) -> jax.Array:
    x_full = jax.lax.all_gather(x_local, axis_name, axis=0)  # (A, m, k_l)
    a, m, kl = x_full.shape
    x_full = jnp.moveaxis(x_full, 0, 1).reshape(m, a * kl)
    return jnp.dot(x_full, w_local,
                   preferred_element_type=jnp.float32).astype(x_local.dtype)


def naive_matmul_rs(x_local: jax.Array, w_local: jax.Array,
                    axis_name: str) -> jax.Array:
    y = jnp.dot(x_local, w_local, preferred_element_type=jnp.float32)
    y = jax.lax.psum(y, axis_name)
    a = _axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    nl = y.shape[1] // a
    return jax.lax.dynamic_slice_in_dim(y, idx * nl, nl, axis=1
                                        ).astype(x_local.dtype)


# ---------------------------------------------------------------------------
# Jit-level helpers that wrap the ring ops in shard_map for a 1-D mesh axis.
# ---------------------------------------------------------------------------
def tp_matmul_overlapped(x: jax.Array, w: jax.Array, mesh: Mesh,
                         axis: str = "model") -> jax.Array:
    """y = x @ w with x k-sharded and w n-sharded on `axis`, overlapped."""
    fn = shard_map(
        functools.partial(ring_ag_matmul, axis_name=axis),
        mesh=mesh,
        in_specs=(P(None, axis), P(None, axis)),
        out_specs=P(None, axis),
    )
    return fn(x, w)
