"""Dispatch from the model stack to the Pallas kernels or their oracles.

The backend decides, not a setting: on ``jax.default_backend() == "tpu"``
every function here runs its compiled Pallas kernel (``interpret=False``);
on every other backend it runs the pure-jnp oracle from
:mod:`repro.kernels.ref`. Interpret mode is only the kernels' own
``interpret=True`` argument, which the kernel tests pass.

Two cases stay on the jnp paths on the TPU too:

* :func:`attention` with ``kv_len`` or ``q_offset`` (masked or offset
  attention). The serving path never makes it: prefill is plain causal
  self-attention and decode goes through :func:`decode_attention`.
* a program traced under a sharding mesh of more than one device
  (:func:`repro.distributed.sharding.use_sharding`). The kernels are not
  wrapped in ``shard_map``, so a sharded program keeps the XLA paths and
  their layouts.

Prefill and the SSD scan take any sequence length: prefill's TPU branch
pads to the kernel's tile, the SSD scan pads to its chunk on every
backend, and both slice the result back.

A ``pallas_call`` has a JVP but no transpose rule, so every kernel here
is differentiated as its oracle: the backward pass recomputes the
``ref.*`` function on the same inputs and takes its VJP. Training on the
TPU runs the kernels forward and the oracles backward.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.distributed.sharding import active_mesh
from repro.kernels import ref as _ref
from repro.kernels.decode_attention import BLOCK_K as DECODE_BLOCK
from repro.kernels.decode_attention import decode_attention as _pl_decode
from repro.kernels.flash_attention import BLOCK_Q as PREFILL_BLOCK
from repro.kernels.flash_attention import flash_attention as _pl_flash
from repro.kernels.int8_matmul import int8_matmul as _pl_int8
from repro.kernels.rmsnorm import rmsnorm as _pl_rmsnorm
from repro.kernels.ssd_scan import ssd_scan as _pl_ssd


def on_tpu() -> bool:
    """Whether the Pallas kernels run: the default backend is a TPU and
    no mesh of more than one device is active."""
    if jax.default_backend() != "tpu":
        return False
    mesh = active_mesh()
    return mesh is None or mesh.size == 1


def _grad_by_ref(kernel, reference, *args):
    """``kernel(*args)``, differentiated as ``reference(*args)``."""
    @jax.custom_vjp
    def f(*a):
        return kernel(*a)

    def fwd(*a):
        return kernel(*a), a

    def bwd(a, g):
        return jax.vjp(reference, *a)[1](g)

    f.defvjp(fwd, bwd)
    return f(*args)


def cache_len(n: int) -> int:
    """The decode cache length that holds ``n`` positions and tiles into
    the decode kernel's KV blocks (``n`` itself up to one block)."""
    if n <= DECODE_BLOCK:
        return n
    return -(-n // DECODE_BLOCK) * DECODE_BLOCK


# ---------------------------------------------------------------------------
def rmsnorm(x, w, eps: float = 1e-5):
    if on_tpu():
        return _grad_by_ref(functools.partial(_pl_rmsnorm, eps=eps),
                            functools.partial(_ref.rmsnorm_ref, eps=eps),
                            x, w)
    return _ref.rmsnorm_ref(x, w, eps)


def attention(q, k, v, *, causal: bool = True, scale: Optional[float] = None,
              q_offset: int = 0, kv_len=None):
    """q: (b, s, hq, d); k, v: (b, s, hkv, d)."""
    if on_tpu() and kv_len is None and not q_offset:
        # Pad the sequence to the kernel's block and slice it back. Under
        # the causal mask the padded keys sit after every real query.
        s = q.shape[1]
        pad = (-s) % PREFILL_BLOCK
        if pad and not causal:
            raise ValueError(f"non-causal attention needs s % "
                             f"{PREFILL_BLOCK} == 0 on the TPU, got s={s}")
        if pad:
            widths = ((0, 0), (0, pad), (0, 0), (0, 0))
            q, k, v = (jnp.pad(t, widths) for t in (q, k, v))
        return _grad_by_ref(
            functools.partial(_pl_flash, causal=causal, scale=scale),
            functools.partial(_ref.attention_ref, causal=causal, scale=scale),
            q, k, v)[:, :s]
    return _ref.attention_ref(q, k, v, causal=causal, scale=scale,
                              q_offset=q_offset, kv_len=kv_len)


def decode_attention(q, k, v, length, layer=None, *,
                     scale: Optional[float] = None):
    """q: (b, hq, d); k, v: the head-major cache, (b, hkv, L, d), or
    stacked over layers, (layers, b, hkv, L, d), with ``layer`` picking
    one; L from :func:`cache_len`. The kernel reads the stacked cache in
    place."""
    if on_tpu():
        layer = jnp.asarray(0 if layer is None else layer, jnp.int32)
        return _grad_by_ref(
            functools.partial(_pl_decode, scale=scale),
            functools.partial(_ref.decode_attention_ref, scale=scale),
            q, k, v, length, layer)
    return _ref.decode_attention_ref(q, k, v, length, layer, scale=scale)


def int8_matmul(x_q, sx, w_q, sw, out_dtype=jnp.float32):
    if on_tpu():
        return _grad_by_ref(
            functools.partial(_pl_int8, out_dtype=out_dtype),
            lambda *a: _ref.int8_matmul_ref(*a).astype(out_dtype),
            x_q, sx, w_q, sw)
    return _ref.int8_matmul_ref(x_q, sx, w_q, sw).astype(out_dtype)


def ssd(x, dt, A, B, C, D, *, chunk: int = 128):
    """Returns (y, final_state (b,h,p,n) fp32), at any sequence length."""
    # Padded steps have dt = 0: no decay and no input, so the real
    # outputs and the final state are those of the unpadded sequence.
    s = x.shape[1]
    pad = (-s) % chunk
    if pad:
        x, dt, B, C = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (x, dt, B, C))
    if on_tpu():
        y, state = _grad_by_ref(
            functools.partial(_pl_ssd, chunk=chunk),
            functools.partial(_ref.ssd_chunked, chunk=chunk),
            x, dt, A, B, C, D)
    else:
        y, state = _ref.ssd_chunked(x, dt, A, B, C, D, chunk=chunk)
    return y[:, :s], state


quantize_int8 = _ref.quantize_int8
