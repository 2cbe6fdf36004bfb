"""Top-k Mixture-of-Experts: capacity-bounded scatter dispatch for
training, a dropless sorted dispatch for prefill and decode.

The layer holds ``MoEConfig.held`` experts from ``expert_first`` (the
chip's share under expert parallelism; all of them by default) and a
shared expert where ``d_ff_shared`` is set. The router keeps all
``num_experts`` outputs and its top-k; each token's top-k weights are
the softmax over its top-k logits (the renormalised top-k of the full
softmax). A share computes its held experts' part of the result for the
tokens routed to them; the shared expert runs on every token.

Serving (``mode`` prefill or decode) drops no token: the (token, choice)
pairs routed to a held expert are sorted by expert and each expert's
SwiGLU runs on its rows only, as one ``jax.lax.ragged_dot`` per matrix
(a grouped matmul, XLA's own instruction on the TPU). The row buffer
holds ``tokens * min(top_k, held)`` rows, the most a share can be
routed, rounded up to whole tiles, so no routing overflows it.

Training keeps the capacity dispatch and holds every expert. Its
TPU-native formulation (GShard-style, grouped): tokens are grouped by their
data shard, positions inside each expert's capacity buffer are computed with
a group-local cumulative sum (no cross-shard prefix), tokens are
scatter-added into an (experts x capacity) buffer (the GSPMD lowering of the
sharded scatter is the MoE all-to-all), experts run as one grouped einsum,
and results gather back weighted by the router's combine weights.

Expert weights are expert-sharded over the ``model`` axis (EP) and
fsdp-sharded over ``data`` on the hidden dim.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig, MoEConfig
from repro.distributed.sharding import active_mesh, shard
from repro.models.layers import dense_init, mlp_apply, mlp_init, mlp_specs

Params = Dict[str, Any]

ROW_TILE = 128   # the dropless dispatch's row buffer is whole tiles of this


def moe_init(rng, cfg: ModelConfig) -> Params:
    """Keys: ``rng -> (router, gate, up, down, shared)``; each of gate, up
    and down splits into one key per expert of the whole layer, of which
    the share takes its own, so a share holds exactly the uncut layer's
    experts."""
    moe = cfg.moe
    assert moe is not None
    d, f, e = cfg.d_model, moe.d_ff_expert, moe.num_experts
    dtype = jnp.dtype(cfg.dtype)
    ks = jax.random.split(rng, 5)
    first = moe.expert_first

    def experts(key, shape):
        keys = jax.random.split(key, e)[first: first + moe.held]
        return jax.vmap(lambda k: dense_init(k, shape, dtype))(keys)

    p = {
        "router": dense_init(ks[0], (d, e), jnp.float32),
        "w_gate": experts(ks[1], (d, f)),
        "w_up": experts(ks[2], (d, f)),
        "w_down": experts(ks[3], (f, d)),
    }
    if moe.d_ff_shared:
        p["shared"] = mlp_init(ks[4], d, moe.d_ff_shared, dtype)
    return p


def moe_specs(cfg: ModelConfig) -> Params:
    p = {
        "router": ("p_embed", None),
        "w_gate": ("p_expert", "p_ff_fsdp", None),
        "w_up": ("p_expert", "p_ff_fsdp", None),
        "w_down": ("p_expert", None, "p_ff_fsdp"),
    }
    if cfg.moe is not None and cfg.moe.d_ff_shared:
        p["shared"] = mlp_specs()
    return p


def _num_groups() -> int:
    """Token groups = number of data-parallel shards (1 without a mesh)."""
    mesh = active_mesh()
    if mesh is None:
        return 1
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    g = sizes.get("data", 1) * sizes.get("pod", 1)
    return g


def expert_capacity(tokens_per_group: int, moe: MoEConfig) -> int:
    c = math.ceil(tokens_per_group * moe.top_k * moe.capacity_factor
                  / moe.num_experts)
    return max(8, -(-c // 8) * 8)  # round up to a multiple of 8


def moe_apply(params: Params, cfg: ModelConfig, x: jax.Array, *,
              mode: str = "train", rng: Optional[jax.Array] = None
              ) -> Tuple[jax.Array, jax.Array]:
    """x: (b, s, d) -> (out, aux_loss). Named scopes: ``router``,
    ``experts`` and ``shared_expert`` in prefill and decode."""
    if mode == "train":
        out, aux = _capacity_apply(params, cfg, x, rng)
    else:
        out, aux = _dropless_apply(params, cfg, x), jnp.zeros((), jnp.float32)
    if "shared" in params:
        with jax.named_scope("shared_expert"):
            out = out + mlp_apply(params["shared"], x)
    return out, aux


def _dropless_apply(params: Params, cfg: ModelConfig, x: jax.Array
                    ) -> jax.Array:
    """The held experts' part of the routed result, for every token."""
    moe = cfg.moe
    b, s, d = x.shape
    t, k, held = b * s, moe.top_k, moe.held
    xt = x.reshape(t, d)
    with jax.named_scope("router"):
        logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                            params["router"])
        top_l, top_i = jax.lax.top_k(logits, k)                 # (t, k)
        weight = jax.nn.softmax(top_l, axis=-1).reshape(t * k)
        # rows of held experts first, by expert; the rest sort last
        local = top_i.reshape(t * k) - moe.expert_first
        group = jnp.where((local >= 0) & (local < held), local, held)
        # whole tiles of 128 rows: XLA on the TPU keeps a ragged dot of
        # such a row count as its own instruction, and expands any other
        # into one dense product per expert
        rows = -(-t * min(k, held) // ROW_TILE) * ROW_TILE
        order = jnp.argsort(group, stable=True)
        order = jnp.pad(order, (0, max(0, rows - t * k)))[:rows]
        sizes = jnp.bincount(group, length=held + 1)[:held]
        live = jnp.arange(rows) < jnp.sum(sizes)
        token = order // k
        weight = jnp.where(live, weight[order], 0.0)
    with jax.named_scope("experts"):
        xs = xt[token]                                          # (rows, d)
        g = jax.lax.ragged_dot(xs, params["w_gate"], sizes)
        u = jax.lax.ragged_dot(xs, params["w_up"], sizes)
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
        y = jax.lax.ragged_dot(h, params["w_down"], sizes)      # (rows, d)
        # rows past the groups are not defined by ragged_dot: select, do
        # not multiply
        y = jnp.where(live[:, None], y.astype(jnp.float32)
                      * weight[:, None], 0.0)
        out = jnp.zeros((t, d), jnp.float32).at[token].add(y)
    return shard(out.astype(x.dtype).reshape(b, s, d),
                 ("batch", "seq", "embed_act"))


def _capacity_apply(params: Params, cfg: ModelConfig, x: jax.Array,
                    rng: Optional[jax.Array]
                    ) -> Tuple[jax.Array, jax.Array]:
    moe = cfg.moe
    assert moe is not None
    if moe.cut:
        raise ValueError("the capacity dispatch holds every expert; "
                         f"this layer holds {moe.held} of {moe.num_experts}")
    b, s, d = x.shape
    e, k = moe.num_experts, moe.top_k
    tokens = b * s
    groups = _num_groups()
    if tokens % groups != 0:
        groups = 1
    tpg = tokens // groups
    cap = expert_capacity(tpg, moe)

    xg = x.reshape(groups, tpg, d)
    xg = shard(xg, ("batch", None, "embed_act"))

    logits = jnp.einsum("gtd,de->gte", xg.astype(jnp.float32),
                        params["router"])
    if moe.router_jitter and rng is not None:
        logits = logits + moe.router_jitter * jax.random.normal(
            rng, logits.shape, jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                     # (g, t, e)
    top_p, top_i = jax.lax.top_k(probs, k)                      # (g, t, k)
    denom = jnp.sum(top_p, axis=-1, keepdims=True)
    combine = top_p / jnp.maximum(denom, 1e-9)

    # Load-balancing aux loss (Switch): E * sum_e f_e * p_e.
    me = jnp.mean(probs, axis=1)                                # (g, e)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(top_i, e, dtype=jnp.float32), axis=2),
        axis=1) / k                                             # (g, e)
    aux = moe.aux_loss_weight * e * jnp.mean(jnp.sum(me * ce, axis=-1))

    # ---- dispatch: k-major position assignment under capacity ----
    # dropped tokens go to one extra overflow row past the e*cap buffer
    buf = jnp.zeros((groups, e * cap + 1, d), x.dtype)
    counts = jnp.zeros((groups, e), jnp.int32)
    dests = []
    keeps = []
    g_iota = jnp.arange(groups)[:, None]
    for kk in range(k):
        idx = top_i[:, :, kk]                                   # (g, t)
        onehot = jax.nn.one_hot(idx, e, dtype=jnp.int32)        # (g, t, e)
        within = jnp.cumsum(onehot, axis=1) - onehot            # exclusive
        pos = jnp.take_along_axis(
            within + counts[:, None, :], idx[..., None], axis=-1)[..., 0]
        keep = pos < cap
        dest = jnp.where(keep, idx * cap + pos, e * cap)        # (g, t)
        buf = buf.at[g_iota, dest].add(
            jnp.where(keep[..., None], xg, 0), mode="drop",
            indices_are_sorted=False, unique_indices=False)
        counts = counts + jnp.sum(onehot, axis=1)
        dests.append(dest)
        keeps.append(keep)

    xb = buf[:, : e * cap].reshape(groups, e, cap, d)
    xb = shard(xb, ("batch", "expert_act", None, "embed_act"))

    # ---- grouped expert SwiGLU ----
    g_h = jnp.einsum("gecd,edf->gecf", xb, params["w_gate"])
    u_h = jnp.einsum("gecd,edf->gecf", xb, params["w_up"])
    h = jax.nn.silu(g_h.astype(jnp.float32)).astype(x.dtype) * u_h
    h = shard(h, ("batch", "expert_act", None, None))
    yb = jnp.einsum("gecf,efd->gecd", h, params["w_down"])
    yb = shard(yb, ("batch", "expert_act", None, "embed_act"))

    # ---- combine ----
    y_flat = jnp.concatenate(
        [yb.reshape(groups, e * cap, d),
         jnp.zeros((groups, 1, d), yb.dtype)], axis=1)
    out = jnp.zeros_like(xg)
    for kk in range(k):
        y_k = y_flat[g_iota, dests[kk]]                         # (g, t, d)
        w_k = (combine[:, :, kk] * keeps[kk]).astype(x.dtype)
        out = out + y_k * w_k[..., None]
    out = out.reshape(b, s, d)
    return shard(out, ("batch", "seq", "embed_act")), aux
