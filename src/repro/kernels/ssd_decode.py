"""Mamba-2 single-token SSD update as a Pallas TPU kernel, in place over
the stacked state and only for the live slots.

The state is stacked over layers, ``(layers, b, h, p, n)`` f32: the
kernel reads it where it lies, at the layer given by scalar prefetch,
and writes the new state back into the same buffer
(``input_output_aliases``), so no layer is sliced out or copied first.

Each block, ``hb`` heads of one slot, is read from HBM once. The update
``h' = exp(dt A) h + dt x (x) B`` and the readout ``y = h' C + D x`` are
both made from the block in VMEM, in f32 on the VPU (products and lane
sums), and ``h'`` is written back once.

The grid is ``(b, h / hb)`` over the slots in ``order``, a permutation
with the ``count`` live slots first (scalar prefetch). Programs past the
last live slot keep that slot's last block index, so Pallas neither
fetches nor writes back state for them; their body only writes zeros
into their own slot's ``y``. A finished slot's state is left as it was
and its ``y`` is 0.

Oracle: ``ref.ssd_decode_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the kernel's name in the compiled program and the device trace
KERNEL_NAME = "ssd_decode"
# bytes of state in one block: double-buffered in and out, four blocks
# stay inside the default scoped VMEM
BLOCK_BYTES = 2 << 20


def head_block(h: int, p: int, n: int) -> int:
    """The most heads of ``p x n`` f32 state that divide ``h`` and fit
    in :data:`BLOCK_BYTES` (one if a single head does not)."""
    per_head = p * n * 4
    return max((d for d in range(1, h + 1)
                if h % d == 0 and d * per_head <= BLOCK_BYTES), default=1)


def _block(i, j, order_ref, count_ref, *, nj: int):
    """Slot and head block of program ``(i, j)``: its own while ``i`` is
    a live slot, then the last live program's."""
    count = count_ref[0]
    slot = order_ref[jnp.clip(i, 0, jnp.maximum(count - 1, 0))]
    return slot, jnp.where(i < count, j, nj - 1)


def _state_index(i, j, order_ref, count_ref, layer_ref, *, nj: int):
    slot, jj = _block(i, j, order_ref, count_ref, nj=nj)
    return layer_ref[0], slot, jj, 0, 0


def _slot_index(i, j, order_ref, count_ref, layer_ref, *, nj: int):
    slot, jj = _block(i, j, order_ref, count_ref, nj=nj)
    return slot, jj, 0, 0


def _kernel(order_ref, count_ref, layer_ref, s_ref, x_ref, dt_ref, a_ref,
            d_ref, b_ref, c_ref, y_ref, o_ref, *, hb: int):
    count = count_ref[0]
    live = pl.program_id(0) < count

    @pl.when(live)
    def _update():
        x = x_ref[...]                               # (p, hb), heads on lanes
        dt = dt_ref[...]                             # (1, hb)
        decay = jnp.exp(dt * a_ref[...])
        dtx = dt * x
        dx = d_ref[...] * x
        B = b_ref[...]                               # (1, n)
        C = c_ref[...]
        for k in range(hb):
            h = s_ref[k] * decay[:, k:k + 1] + dtx[:, k:k + 1] * B   # (p, n)
            o_ref[k] = h
            y_ref[:, k:k + 1] = (jnp.sum(h * C, axis=1, keepdims=True)
                                 + dx[:, k:k + 1])

    @pl.when(jnp.logical_not(live))
    def _finished():
        y_ref[...] = jnp.zeros_like(y_ref)

    # With no live slot every program maps to one block, which is then
    # written back: write it as it was read.
    @pl.when(count == 0)
    def _none_live():
        o_ref[...] = s_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_decode(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
               C: jax.Array, D: jax.Array, state: jax.Array, layer,
               live=None, *, interpret: bool = False):
    """x: (b, h, p); dt: (b, h); A, D: (h,); B, C: (b, n); state:
    (layers, b, h, p, n) f32 with ``layer`` picking one; live: (b,) bool,
    or None for every slot. Returns (y (b, h, p) in x's dtype, the state
    with the layer's live entries updated)."""
    _, b, h, p, n = state.shape
    hb = head_block(h, p, n)
    nj = h // hb
    f32 = jnp.float32
    live = jnp.ones((b,), jnp.bool_) if live is None else live
    order = jnp.argsort(jnp.logical_not(live), stable=True).astype(jnp.int32)
    count = jnp.sum(live, dtype=jnp.int32).reshape(1)

    # the small operands head-blocked, heads on lanes: (.., nj, rows, hb)
    xt = x.astype(f32).reshape(b, nj, hb, p).swapaxes(2, 3)
    dtt = dt.astype(f32).reshape(b, nj, 1, hb)
    At = A.astype(f32).reshape(nj, 1, hb)
    Dt = D.astype(f32).reshape(nj, 1, hb)
    Bt = B.astype(f32).reshape(b, 1, n)
    Ct = C.astype(f32).reshape(b, 1, n)

    state_spec = pl.BlockSpec((None, None, hb, p, n),
                              functools.partial(_state_index, nj=nj))
    slot_spec = functools.partial(_slot_index, nj=nj)

    def head_spec(i, j, *refs):
        return slot_spec(i, j, *refs)[1:]

    def bc_spec(i, j, *refs):
        return slot_spec(i, j, *refs)[0], 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, nj),
        in_specs=[
            state_spec,
            pl.BlockSpec((None, None, p, hb), slot_spec),
            pl.BlockSpec((None, None, 1, hb), slot_spec),
            pl.BlockSpec((None, 1, hb), head_spec),
            pl.BlockSpec((None, 1, hb), head_spec),
            pl.BlockSpec((None, 1, n), bc_spec),
            pl.BlockSpec((None, 1, n), bc_spec),
        ],
        out_specs=[
            pl.BlockSpec((None, None, p, hb),
                         lambda i, j, order_ref, *_: (order_ref[i], j, 0, 0)),
            state_spec,
        ],
    )
    y, state = pl.pallas_call(
        functools.partial(_kernel, hb=hb),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, nj, p, hb), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name=KERNEL_NAME,
        interpret=interpret,
    )(order, count, jnp.asarray(layer, jnp.int32).reshape(1), state, xt,
      dtt, At, Dt, Bt, Ct)
    return y.swapaxes(2, 3).reshape(b, h, p).astype(x.dtype), state
