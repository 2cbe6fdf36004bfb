"""GPipe-style pipeline parallelism under ``shard_map``.

Each device along the ``stage`` axis owns a contiguous chunk of layers
(params pre-stacked with a leading stage dim). Microbatches stream through
the ring: at tick t stage s runs microbatch (t - s), activations hop
stage s -> s+1 via ``lax.ppermute``. Bubble fraction is the usual
(S-1)/(M+S-1); pick M >= 4*S.

This substrate is exercised at smoke scale (multi-device subprocess tests)
and is available via ``TrainConfig``-level wiring for models whose layers
are homogeneous.
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

Params = Any


def _shift_right(x: jax.Array, axis_name: str) -> jax.Array:
    a = jax.lax.psum(1, axis_name)
    perm = [(j, (j + 1) % a) for j in range(a)]
    return jax.lax.ppermute(x, axis_name, perm)


def pipeline_forward(stage_fn: Callable[[Params, jax.Array], jax.Array],
                     stage_params: Params, x_mb: jax.Array,
                     axis_name: str = "stage") -> jax.Array:
    """Run inside shard_map. x_mb: (M, mb, ...) microbatched inputs
    (replicated); stage_params: this stage's params. Returns (M, mb, ...)
    outputs (valid on the last stage; replicated back via ppermute ring).
    """
    s_idx = jax.lax.axis_index(axis_name)
    n_stage = jax.lax.psum(1, axis_name)
    m = x_mb.shape[0]
    ticks = m + n_stage - 1

    def _pvary(v):
        return jax.lax.pcast(v, (axis_name,), to="varying")

    state = _pvary(jnp.zeros_like(x_mb[0]))
    outputs = _pvary(jnp.zeros_like(x_mb))
    x_mb = _pvary(x_mb)

    def body(t, carry):
        state, outputs = carry
        # Stage 0 ingests microbatch t (if any); others take the incoming
        # activation from the previous stage.
        mb_in = jax.lax.dynamic_index_in_dim(
            x_mb, jnp.clip(t, 0, m - 1), axis=0, keepdims=False)
        inp = jnp.where(s_idx == 0, mb_in, state)
        active = (t - s_idx >= 0) & (t - s_idx < m)
        out = stage_fn(stage_params, inp)
        out = jnp.where(active, out, jnp.zeros_like(out))
        # Last stage records its finished microbatch.
        mb_done = t - (n_stage - 1)
        record = (s_idx == n_stage - 1) & (mb_done >= 0) & (mb_done < m)
        outputs = jax.lax.cond(
            record,
            lambda o: jax.lax.dynamic_update_index_in_dim(
                o, out, jnp.clip(mb_done, 0, m - 1), axis=0),
            lambda o: o,
            outputs)
        # Everyone forwards to the next stage.
        state = _shift_right(out, axis_name)
        return state, outputs

    _, outputs = jax.lax.fori_loop(0, ticks, body, (state, outputs))
    # Broadcast results from the last stage to all stages (masked psum is
    # provably replicated under the vma type system).
    outputs = jax.lax.psum(
        jnp.where(s_idx == n_stage - 1, outputs, jnp.zeros_like(outputs)),
        axis_name)
    return outputs


def make_pipelined_fn(stage_fn: Callable, mesh: Mesh, n_stages: int,
                      axis_name: str = "stage"):
    """Wrap stage_fn into a jit-able pipelined callable.

    stage_params must be stacked with a leading (n_stages,) dim; inputs are
    (M, mb, ...) microbatches.
    """
    def run(stacked_params, x_mb):
        fn = shard_map(
            functools.partial(pipeline_forward, stage_fn,
                              axis_name=axis_name),
            mesh=mesh,
            in_specs=(P(axis_name), P()),
            out_specs=P(),
        )
        # Each stage receives its own params slice: leading dim sharded.
        squeezed = jax.tree.map(lambda p: p, stacked_params)
        return fn(squeezed, x_mb)

    def wrapper(stacked_params, x_mb):
        def stage_body(params_slice, x):
            p = jax.tree.map(lambda a: a[0], params_slice)
            return stage_fn(p, x)
        fn = shard_map(
            functools.partial(pipeline_forward, stage_body,
                              axis_name=axis_name),
            mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P(axis_name), stacked_params),
                      P()),
            out_specs=P(),
        )
        return fn(stacked_params, x_mb)

    return wrapper
