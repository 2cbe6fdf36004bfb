"""Mamba-2 (SSD) mixer layer with causal depthwise conv and gated RMSNorm.

Train/prefill run the chunked SSD (``kernels.ops.ssd``: Pallas on TPU,
sequential-scan oracle on CPU); decode runs the O(1) single-token
recurrence carrying (conv_state, ssd_state), the SSD part through
``kernels.ops.ssd_decode`` (on TPU in place, for the live slots only).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.distributed.sharding import shard
from repro.kernels import ops
from repro.models.layers import dense_init

Params = Dict[str, Any]


def _dims(cfg: ModelConfig):
    m = cfg.mamba
    assert m is not None
    di = m.d_inner(cfg.d_model)
    nh = m.n_heads(cfg.d_model)
    return m, di, nh


def mamba_init(rng, cfg: ModelConfig) -> Params:
    m, di, nh = _dims(cfg)
    d, n = cfg.d_model, m.d_state
    dtype = jnp.dtype(cfg.dtype)
    ks = jax.random.split(rng, 4)
    conv_dim = di + 2 * n
    return {
        # in_proj emits [z (di), x (di), B (n), C (n), dt (nh)]
        "w_in": dense_init(ks[0], (d, 2 * di + 2 * n + nh), dtype),
        "conv_w": dense_init(ks[1], (m.d_conv, conv_dim), dtype, scale=0.1),
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, nh).astype(jnp.float32)),
        "dt_bias": jnp.zeros((nh,), jnp.float32),
        "D": jnp.ones((nh,), jnp.float32),
        "norm_scale": jnp.ones((di,), jnp.float32),
        "w_out": dense_init(ks[3], (di, d), dtype),
    }


def mamba_specs(cfg: ModelConfig) -> Params:
    return {
        "w_in": ("p_embed", "p_inner"),
        "conv_w": (None, "p_inner"),
        "conv_b": ("p_inner",),
        "A_log": (None,),
        "dt_bias": (None,),
        "D": (None,),
        "norm_scale": ("p_inner",),
        "w_out": ("p_inner", "p_embed"),
    }


def init_mamba_cache(cfg: ModelConfig, batch: int, dtype) -> Params:
    m, di, nh = _dims(cfg)
    conv_dim = di + 2 * m.d_state
    return {
        "conv": jnp.zeros((batch, m.d_conv - 1, conv_dim), dtype),
        "ssd": jnp.zeros((batch, nh, m.headdim, m.d_state), jnp.float32),
    }


def mamba_cache_specs() -> Params:
    return {
        "conv": ("batch", None, "mlp_act"),
        "ssd": ("batch", "heads_act", None, None),
    }


def _split_proj(cfg: ModelConfig, proj: jax.Array):
    m, di, nh = _dims(cfg)
    n = m.d_state
    z = proj[..., :di]
    xbc = proj[..., di: 2 * di + 2 * n]
    dt = proj[..., 2 * di + 2 * n:]
    return z, xbc, dt


def _causal_conv(xbc: jax.Array, w: jax.Array, b: jax.Array,
                 init: Optional[jax.Array] = None) -> jax.Array:
    """Depthwise causal conv. xbc: (b, s, c); w: (k, c)."""
    k = w.shape[0]
    if init is None:
        pad = jnp.zeros((xbc.shape[0], k - 1, xbc.shape[2]), xbc.dtype)
    else:
        pad = init.astype(xbc.dtype)
    xp = jnp.concatenate([pad, xbc], axis=1)
    out = jnp.zeros_like(xbc, dtype=jnp.float32)
    for i in range(k):
        out = out + xp[:, i: i + xbc.shape[1]].astype(jnp.float32) \
            * w[i].astype(jnp.float32)[None, None, :]
    out = out + b.astype(jnp.float32)[None, None, :]
    return jax.nn.silu(out).astype(xbc.dtype)


def _gated_norm(y: jax.Array, z: jax.Array, scale: jax.Array,
                eps: float) -> jax.Array:
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    var = jnp.mean(g * g, axis=-1, keepdims=True)
    return (g * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
            ).astype(y.dtype)


@jax.named_scope("mamba")
def mamba_apply(params: Params, cfg: ModelConfig, x: jax.Array, *,
                mode: str, cache: Optional[Params] = None, layer=None,
                live=None) -> Tuple[jax.Array, Optional[Params]]:
    """x: (b, s, d) -> (out, new_cache). In decode, ``cache`` is stacked
    over layers and ``layer`` indexes it; the conv window is read at that
    index and written back there whole, and ``ops.ssd_decode`` updates the
    SSD state of the ``live`` slots ((b,) bool, None for all) there.
    Named scopes: ``mamba`` around it all, ``conv``, ``ssd`` and
    ``gated_norm`` inside."""
    m, di, nh = _dims(cfg)
    n, p = m.d_state, m.headdim
    b, s, d = x.shape
    proj = jnp.einsum("bsd,de->bse", x, params["w_in"])
    proj = shard(proj, ("batch", "seq", "mlp_act"))
    z, xbc, dt_raw = _split_proj(cfg, proj)
    A = -jnp.exp(params["A_log"])

    if mode in ("train", "prefill"):
        with jax.named_scope("conv"):
            xbc_c = _causal_conv(xbc, params["conv_w"], params["conv_b"])
        xs = xbc_c[..., :di].reshape(b, s, nh, p)
        B = xbc_c[..., di: di + n]
        C = xbc_c[..., di + n:]
        with jax.named_scope("ssd"):
            dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                                 + params["dt_bias"][None, None])
            y, state = ops.ssd(xs, dt, A, B, C, params["D"],
                               chunk=m.chunk_size)
        y = y.reshape(b, s, di)
        new_cache = None
        if mode == "prefill":
            new_cache = {
                "conv": xbc[:, s - (m.d_conv - 1):].astype(x.dtype)
                if s >= m.d_conv - 1 else jnp.pad(
                    xbc, ((0, 0), (m.d_conv - 1 - s, 0), (0, 0))),
                "ssd": state,
            }
    else:  # decode: s == 1
        assert cache is not None and layer is not None
        conv = jax.lax.dynamic_index_in_dim(cache["conv"], layer,
                                            keepdims=False)
        with jax.named_scope("conv"):
            conv_hist = jnp.concatenate([conv, xbc], axis=1)
            w, bias = params["conv_w"], params["conv_b"]
            acc = jnp.einsum("bkc,kc->bc", conv_hist.astype(jnp.float32),
                             w.astype(jnp.float32))
            acc = jax.nn.silu(acc + bias.astype(jnp.float32))
            xbc_c = acc[:, None].astype(x.dtype)
        xs = xbc_c[..., :di].reshape(b, nh, p)
        B = xbc_c[:, 0, di: di + n]
        C = xbc_c[:, 0, di + n:]
        with jax.named_scope("ssd"):
            dt = jax.nn.softplus(dt_raw[:, 0].astype(jnp.float32)
                                 + params["dt_bias"][None])
            y1, ssd = ops.ssd_decode(xs, dt, A, B, C, params["D"],
                                     cache["ssd"], layer, live)
        y = y1.reshape(b, 1, di)
        new_cache = {
            "conv": jax.lax.dynamic_update_index_in_dim(
                cache["conv"], conv_hist[:, 1:].astype(cache["conv"].dtype),
                layer, 0),
            "ssd": ssd,
        }

    with jax.named_scope("gated_norm"):
        y = _gated_norm(y, z, params["norm_scale"], cfg.norm_eps)
    y = shard(y, ("batch", "seq", "mlp_act"))
    return jnp.einsum("bse,ed->bsd", y, params["w_out"]), new_cache
