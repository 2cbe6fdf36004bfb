"""Pure-jnp oracles for every Pallas kernel.

These are the semantics of record: each Pallas kernel in this package is
asserted allclose against the function here across shape/dtype sweeps
(tests/test_kernels_*.py), and the model stack uses these implementations
on non-TPU backends.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------
def rmsnorm_ref(x: jax.Array, w: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------------
# Attention (prefill / train): GQA, causal or full.
# q: (b, sq, hq, d)   k, v: (b, skv, hkv, d)
# ---------------------------------------------------------------------------
def attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool = True, scale: float | None = None,
                  q_offset: int = 0, kv_len: jax.Array | None = None
                  ) -> jax.Array:
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    assert hq % hkv == 0, (hq, hkv)
    g = hq // hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    qf = q.reshape(b, sq, hkv, g, d).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qf, kf) * scale
    mask = None
    if causal:
        qi = jnp.arange(sq)[:, None] + q_offset
        ki = jnp.arange(skv)[None, :]
        mask = qi >= ki
    if kv_len is not None:
        lmask = jnp.arange(skv)[None, :] < jnp.asarray(kv_len).reshape(-1, 1)
        lmask = lmask.reshape(b, 1, 1, 1, skv)
        scores = jnp.where(lmask, scores, NEG_INF)
    if mask is not None:
        scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, vf)
    return out.reshape(b, sq, hq, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# Decode attention: single query token against a (possibly longer) cache.
# q: (b, hq, d)   length: (b,) valid cache length
# k, v: (b, hkv, skv, d), head-major, or stacked over layers as
#       (layers, b, hkv, skv, d) with ``layer`` picking one
# ---------------------------------------------------------------------------
def _decode_layer(k: jax.Array, v: jax.Array, layer):
    if k.ndim == 4:
        return k, v
    return (jax.lax.dynamic_index_in_dim(t, layer, keepdims=False)
            for t in (k, v))


def decode_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                         length: jax.Array, layer=None, *,
                         scale: float | None = None) -> jax.Array:
    k, v = _decode_layer(k, v, layer)
    b, hq, d = q.shape
    _, hkv, skv, _ = k.shape
    g = hq // hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    qf = q.reshape(b, hkv, g, d).astype(jnp.float32)
    scores = jnp.einsum("bhgd,bhkd->bhgk", qf, k.astype(jnp.float32)) * scale
    lmask = jnp.arange(skv)[None, :] < jnp.asarray(length).reshape(-1, 1)
    scores = jnp.where(lmask[:, None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgk,bhkd->bhgd", p, v.astype(jnp.float32))
    return out.reshape(b, hq, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# Int8 W8A8 matmul with per-channel scales (the paper's INT8-on-DSP analog).
# x_q: (m, k) int8, sx: (m,) f32;  w_q: (k, n) int8, sw: (n,) f32
# ---------------------------------------------------------------------------
def int8_matmul_ref(x_q: jax.Array, sx: jax.Array, w_q: jax.Array,
                    sw: jax.Array) -> jax.Array:
    acc = jnp.matmul(x_q.astype(jnp.int32), w_q.astype(jnp.int32),
                     preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * sx[:, None] * sw[None, :]


def quantize_int8(x: jax.Array, axis: int = -1) -> tuple[jax.Array, jax.Array]:
    """Symmetric per-row (along `axis` reduced) int8 quantization."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=axis, keepdims=True)
    scale = jnp.where(amax == 0, 1.0, amax / 127.0)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), jnp.squeeze(scale, axis=axis)


# ---------------------------------------------------------------------------
# Mamba-2 SSD: sequential-recurrence oracle.
# x:  (b, s, h, p)    per-head inputs (p = headdim)
# dt: (b, s, h)       positive step sizes (already softplus'ed + bias)
# A:  (h,)            negative per-head decay rates
# B:  (b, s, n)       shared across heads (ngroups=1), n = d_state
# C:  (b, s, n)
# D:  (h,)            skip
# Returns y: (b, s, h, p) and final state (b, h, p, n).
# ---------------------------------------------------------------------------
def ssd_ref(x, dt, A, B, C, D, init_state=None):
    b, s, h, p = x.shape
    n = B.shape[-1]
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    Af = A.astype(jnp.float32)
    Bf = B.astype(jnp.float32)
    Cf = C.astype(jnp.float32)
    decay = jnp.exp(dtf * Af[None, None, :])            # (b, s, h)
    if init_state is None:
        init_state = jnp.zeros((b, h, p, n), jnp.float32)

    def step(state, t):
        a_t = decay[:, t]                                # (b, h)
        dbx = jnp.einsum("bh,bhp,bn->bhpn", dtf[:, t], xf[:, t], Bf[:, t])
        state = state * a_t[..., None, None] + dbx
        y_t = jnp.einsum("bhpn,bn->bhp", state, Cf[:, t])
        return state, y_t

    state, ys = jax.lax.scan(step, init_state.astype(jnp.float32),
                             jnp.arange(s))
    y = jnp.moveaxis(ys, 0, 1)                           # (b, s, h, p)
    y = y + xf * D.astype(jnp.float32)[None, None, :, None]
    return y.astype(x.dtype), state


def ssd_decode_ref(x, dt, A, B, C, D, state):
    """One-token SSD recurrence. x: (b,h,p), dt: (b,h), B/C: (b,n),
    state: (b,h,p,n) -> (y, new_state)."""
    xf, dtf = x.astype(jnp.float32), dt.astype(jnp.float32)
    a = jnp.exp(dtf * A.astype(jnp.float32)[None, :])
    dbx = jnp.einsum("bh,bhp,bn->bhpn", dtf, xf, B.astype(jnp.float32))
    state = state * a[..., None, None] + dbx
    y = jnp.einsum("bhpn,bn->bhp", state, C.astype(jnp.float32))
    y = y + xf * D.astype(jnp.float32)[None, :, None]
    return y.astype(x.dtype), state


def ssd_chunked(x, dt, A, B, C, D, chunk: int = 256, init_state=None):
    """Vectorized chunked SSD (same math as the Pallas kernel) in pure jnp.

    This is the production non-Pallas path: the scan runs over s/chunk
    boundaries only, so the backward pass stashes O(s/chunk) states instead
    of O(s) (the sequential oracle ``ssd_ref`` keeps one per timestep).
    """
    b, s, h, p = x.shape
    n = B.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    f32 = jnp.float32
    xr = x.astype(f32).reshape(b, nc, chunk, h, p)
    dtr = dt.astype(f32).reshape(b, nc, chunk, h)
    Br = B.astype(f32).reshape(b, nc, chunk, n)
    Cr = C.astype(f32).reshape(b, nc, chunk, n)
    Af = A.astype(f32)

    l = dtr * Af[None, None, None, :]                    # (b,nc,Q,h)
    L = jnp.cumsum(l, axis=2)                            # inclusive
    # intra-chunk: M[t,j] = (C_t.B_j) exp(L_t - L_j) [j<=t]
    cb = jnp.einsum("bctn,bcjn->bctj", Cr, Br)           # (b,nc,Q,Q)
    logdec = L[:, :, :, None, :] - L[:, :, None, :, :]   # (b,nc,Q,Q,h)
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))
    M = cb[..., None] * jnp.exp(
        jnp.where(tri[None, None, :, :, None], logdec, NEG_INF))
    y_intra = jnp.einsum("bctjh,bcjh,bcjhp->bcthp", M, dtr, xr)

    # chunk summaries: G_c = sum_j exp(L_last - L_j) dt_j B_j (x) x_j
    w = jnp.exp(L[:, :, -1:, :] - L) * dtr               # (b,nc,Q,h)
    G = jnp.einsum("bcjn,bcjh,bcjhp->bchnp", Br, w, xr)  # (b,nc,h,n,p)
    a_chunk = jnp.exp(L[:, :, -1])                       # (b,nc,h)

    if init_state is None:
        init_state = jnp.zeros((b, h, p, n), f32)
    st0 = jnp.swapaxes(init_state, -1, -2)               # (b,h,n,p)

    def step(carry, inp):
        g_c, a_c = inp                                   # (b,h,n,p),(b,h)
        h_in = carry
        h_out = h_in * a_c[..., None, None] + g_c
        return h_out, h_in                               # emit state BEFORE

    (h_last, h_ins) = jax.lax.scan(
        step, st0, (jnp.moveaxis(G, 1, 0), jnp.moveaxis(a_chunk, 1, 0)))
    h_ins = jnp.moveaxis(h_ins, 0, 1)                    # (b,nc,h,n,p)
    y_inter = jnp.einsum("bctn,bcth,bchnp->bcthp",
                         Cr, jnp.exp(L), h_ins)
    y = (y_intra + y_inter).reshape(b, s, h, p)
    y = y + x.astype(f32) * D.astype(f32)[None, None, :, None]
    return y.astype(x.dtype), jnp.swapaxes(h_last, -1, -2)


# ---------------------------------------------------------------------------
# SwiGLU
# ---------------------------------------------------------------------------
def swiglu_ref(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
               w_down: jax.Array) -> jax.Array:
    g = jnp.einsum("...d,df->...f", x, w_gate)
    u = jnp.einsum("...d,df->...f", x, w_up)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    return jnp.einsum("...f,fd->...d", h, w_down)
