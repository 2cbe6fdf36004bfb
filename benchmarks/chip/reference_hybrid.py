"""Plain float32 reference of the served hybrid decoder
(granite-4.0-h-small), for the output check.

It imports nothing of the program and takes nothing the program made.
It builds the weights itself from the seed, one layer at a time, by the
initialisation the program documents (``models/``): every matrix is
``N(0, 1) * 0.02`` drawn in float32 and stored in the served dtype
(bfloat16), but the conv kernel (``* 0.1``), the embedding (``* 0.02 /
embedding_multiplier``) and the router (float32); the conv bias and
``dt_bias`` are 0, ``D`` and every norm scale 1, and ``A_log =
log(linspace(1, 16, heads))``. The keys split as

    root -> (embed, stack); embed -> (embedding, unused);
    stack -> one key per layer -> (mixer, moe);
    Mamba mixer -> (in_proj, conv, unused, out_proj);
    attention -> (wq, wk, wv, wo);
    moe -> (router, gate, up, down, shared); gate, up and down -> one key
    per expert of the whole layer, of which the held experts take
    theirs; shared -> (gate, up, down).

The forward pass follows the published description of
``granitemoehybrid``: the embeddings times ``embedding_multiplier``;
in each layer a pre-norm RMSNorm, the mixer, and ``x + branch *
residual_multiplier``, then a pre-norm RMSNorm, the MoE block plus the
shared expert (both SwiGLU), and again ``x + branch *
residual_multiplier``; a final RMSNorm and the tied head, divided by
``logits_scaling``.

* The Mamba-2 mixer is written as its recurrence, one step at a time:
  in-projection to (z, x, B, C, dt), a causal depthwise conv of width
  ``mamba_d_conv`` with bias and SiLU over (x, B, C), ``dt =
  softplus(dt + dt_bias)``, ``A = -exp(A_log)``, then per head ``h_t =
  exp(dt_t A) h_{t-1} + dt_t x_t B_t^T`` and ``y_t = h_t C_t + D x_t``
  (one group: B and C shared by the heads), the gated RMSNorm
  ``rms(y * silu(z))``, and the out-projection.
* Attention is causal grouped-query attention without positions
  (NoPE), the scores scaled by ``attention_multiplier``.
* The router has all published experts' outputs; each token takes its
  top ``num_experts_per_tok`` logits, weighted by their softmax. Only the
  experts held here are computed, each on every token, and a token's
  other experts add nothing.

Every product is float32 at ``Precision.HIGHEST``.

Departures from the published model, all shared with the program: the
weights are random (Granite's ``A_log`` and ``dt`` initialisation are
not used, and the embedding is drawn ``embedding_multiplier`` times
smaller than the other matrices, or the random tied model would only
repeat its last token); the depth is the file's cut
(``num_hidden_layers`` of the pattern); and only ``num_local_experts``
experts from ``deployment.expert_first`` are computed.

``gaps`` returns, for each served token, how far its reference logit
lies below the reference's best logit at that position.
"""
from __future__ import annotations

import functools
from typing import List, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
SEQ_BLOCK = 512      # sequences are padded to a multiple of this
Q_CHUNK = 512        # queries scored at once


class Spec(NamedTuple):
    kinds: Tuple[str, ...]       # per layer: "mamba" or "attention"
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    eps: float
    m_heads: int
    m_head_dim: int
    m_state: int
    m_conv: int
    experts: int
    top_k: int
    d_expert: int
    d_shared: int
    first: int                   # the first expert held here
    held: int
    embedding_multiplier: float
    residual_multiplier: float
    attention_multiplier: float
    logits_scaling: float
    dtype: str = "bfloat16"      # the stored weights

    @classmethod
    def from_conf(cls, conf: dict) -> "Spec":
        heads = conf["num_attention_heads"]
        assert conf["mamba_n_groups"] == 1
        assert conf["tie_word_embeddings"]
        assert conf["position_embedding_type"] == "nope"
        assert (conf["mamba_expand"] * conf["hidden_size"]
                == conf["mamba_n_heads"] * conf["mamba_d_head"])
        dep = conf["deployment"]
        return cls(
            kinds=tuple(conf["layer_types"]), d=conf["hidden_size"],
            heads=heads, kv_heads=conf["num_key_value_heads"],
            head_dim=conf.get("head_dim") or conf["hidden_size"] // heads,
            vocab=conf["vocab_size"], eps=float(conf["rms_norm_eps"]),
            m_heads=conf["mamba_n_heads"], m_head_dim=conf["mamba_d_head"],
            m_state=conf["mamba_d_state"], m_conv=conf["mamba_d_conv"],
            experts=dep["experts_published"],
            top_k=conf["num_experts_per_tok"],
            d_expert=conf["intermediate_size"],
            d_shared=conf["shared_intermediate_size"],
            first=dep["expert_first"], held=conf["num_local_experts"],
            embedding_multiplier=float(conf["embedding_multiplier"]),
            residual_multiplier=float(conf["residual_multiplier"]),
            attention_multiplier=float(conf["attention_multiplier"]),
            logits_scaling=float(conf["logits_scaling"]))


def _w(spec: Spec, key, shape, scale=0.02):
    """A stored weight: float32 normal * scale, rounded to the stored
    dtype."""
    return (jax.random.normal(key, shape, jnp.float32) * scale
            ).astype(spec.dtype).astype(jnp.float32)


def _mm(eq, a, b):
    return jnp.einsum(eq, a, b, precision=HI)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _swiglu(h, wg, wu, wd):
    return _mm("sf,fd->sd", jax.nn.silu(_mm("sd,df->sf", h, wg))
               * _mm("sd,df->sf", h, wu), wd)


@functools.partial(jax.jit, static_argnums=(0,))
def _mamba(spec: Spec, x, key):
    """One Mamba-2 mixer over the sequence x: (S, d), as a recurrence."""
    d, nh, p, n, k = (spec.d, spec.m_heads, spec.m_head_dim, spec.m_state,
                      spec.m_conv)
    di = nh * p
    conv_dim = di + 2 * n
    kin, kconv, _, kout = jax.random.split(key, 4)
    proj = _mm("sd,de->se", _rms(x, spec.eps),
               _w(spec, kin, (d, 2 * di + 2 * n + nh)))
    z, xbc, dt = proj[:, :di], proj[:, di: di + conv_dim], proj[:, di + conv_dim:]
    wc = _w(spec, kconv, (k, conv_dim), 0.1)
    s = x.shape[0]
    padded = jnp.concatenate([jnp.zeros((k - 1, conv_dim)), xbc])
    conv = sum(padded[i: i + s] * wc[i] for i in range(k))  # bias is 0
    xbc = jax.nn.silu(conv)
    xs = xbc[:, :di].reshape(s, nh, p)
    B, C = xbc[:, di: di + n], xbc[:, di + n:]
    dt = jax.nn.softplus(dt)                                # dt_bias is 0
    A = -jnp.exp(jnp.log(jnp.linspace(1.0, 16.0, nh)))
    D = jnp.ones((nh,))

    def step(h, t):
        h = (jnp.exp(dt[t] * A)[:, None, None] * h
             + (dt[t][:, None] * xs[t])[:, :, None] * B[t][None, None, :])
        return h, _mm("hpn,n->hp", h, C[t]) + D[:, None] * xs[t]

    _, y = jax.lax.scan(step, jnp.zeros((nh, p, n)), jnp.arange(s))
    g = y.reshape(s, di) * jax.nn.silu(z)
    return _mm("se,ed->sd", _rms(g, spec.eps), _w(spec, kout, (di, d)))


@functools.partial(jax.jit, static_argnums=(0,))
def _attention(spec: Spec, x, key):
    """Causal NoPE GQA over x: (S, d), S a multiple of Q_CHUNK."""
    d, hq, hkv, hd = spec.d, spec.heads, spec.kv_heads, spec.head_dim
    kq, kk, kv, ko = jax.random.split(key, 4)
    h = _rms(x, spec.eps)
    q = _mm("sd,dhk->shk", h, _w(spec, kq, (d, hq, hd)))
    k = _mm("sd,dhk->shk", h, _w(spec, kk, (d, hkv, hd)))
    v = _mm("sd,dhk->shk", h, _w(spec, kv, (d, hkv, hd)))
    s = x.shape[0]
    qg = q.reshape(s // Q_CHUNK, Q_CHUNK, hkv, hq // hkv, hd)
    cols = jnp.arange(s)

    def chunk(args):
        i, qc = args
        sc = _mm("qkgd,tkd->kgqt", qc, k) * spec.attention_multiplier
        rows = i * Q_CHUNK + jnp.arange(Q_CHUNK)
        sc = jnp.where(cols[None, :] <= rows[:, None], sc, -jnp.inf)
        return _mm("kgqt,tkd->qkgd", jax.nn.softmax(sc, axis=-1), v)

    o = jax.lax.map(chunk, (jnp.arange(s // Q_CHUNK), qg)).reshape(s, hq, hd)
    return _mm("shk,hkd->sd", o, _w(spec, ko, (hq, hd, d)))


@functools.partial(jax.jit, static_argnums=(0,))
def _moe(spec: Spec, x, key):
    """The held experts' part of the routed block plus the shared
    expert, over x: (S, d)."""
    d, f, e = spec.d, spec.d_expert, spec.experts
    kr, kg, ku, kd, ks = jax.random.split(key, 5)
    h = _rms(x, spec.eps)
    logits = _mm("sd,de->se", h,
                 jax.random.normal(kr, (d, e), jnp.float32) * 0.02)
    top_l, top_i = jax.lax.top_k(logits, spec.top_k)
    weight = jax.nn.softmax(top_l, axis=-1)
    held = slice(spec.first, spec.first + spec.held)
    keys = [jax.random.split(k, e)[held] for k in (kg, ku, kd)]
    out = 0.0
    for j in range(spec.held):
        w_j = jnp.sum(jnp.where(top_i == spec.first + j, weight, 0.0), -1)
        y_j = _swiglu(h, _w(spec, keys[0][j], (d, f)),
                      _w(spec, keys[1][j], (d, f)),
                      _w(spec, keys[2][j], (f, d)))
        out = out + w_j[:, None] * y_j
    sg, su, sd = jax.random.split(ks, 3)
    fs = spec.d_shared
    return out + _swiglu(h, _w(spec, sg, (d, fs)), _w(spec, su, (d, fs)),
                         _w(spec, sd, (fs, d)))


def _embedding_scale(spec: Spec) -> float:
    return 0.02 / spec.embedding_multiplier


@functools.partial(jax.jit, static_argnums=(0,))
def _embed(spec: Spec, root, tokens):
    k_emb = jax.random.split(jax.random.split(root)[0])[0]
    return _w(spec, k_emb, (spec.vocab, spec.d), _embedding_scale(spec)
              )[tokens] * spec.embedding_multiplier


@functools.partial(jax.jit, static_argnums=(0,))
def _head(spec: Spec, root, x, at):
    k_emb = jax.random.split(jax.random.split(root)[0])[0]
    emb = _w(spec, k_emb, (spec.vocab, spec.d), _embedding_scale(spec))
    return _mm("md,vd->mv", _rms(x[at], spec.eps), emb) / spec.logits_scaling


def logits(spec: Spec, root, tokens, at):
    """tokens: (S,) padded to a multiple of SEQ_BLOCK; at: (M,)
    positions. Returns the reference's logits there, (M, vocab)."""
    x = _embed(spec, root, tokens)
    keys = jax.random.split(jax.random.split(root)[1], len(spec.kinds))
    rm = spec.residual_multiplier
    for kind, key in zip(spec.kinds, keys):
        k_mix, k_moe = jax.random.split(key)
        mixer = _mamba if kind == "mamba" else _attention
        x = x + rm * mixer(spec, x, k_mix)
        x = x + rm * _moe(spec, x, k_moe)
    return _head(spec, root, x, at)


@jax.jit
def _gap_of(logits, chosen):
    best = jnp.max(logits, axis=-1)
    return best - jnp.take_along_axis(logits, chosen[:, None], axis=-1)[:, 0]


def gaps(spec: Spec, seed32: int, prompts: Sequence[np.ndarray],
         served: Sequence[Sequence[int]], max_new: int) -> List[np.ndarray]:
    """For each request, the gap of each served token below the
    reference's best logit at its position."""
    root = jax.random.key(seed32)
    out = []
    for prompt, toks in zip(prompts, served):
        toks = np.asarray(toks, np.int32)
        n, m = len(prompt), len(toks)
        seq = np.concatenate([prompt, toks[:-1]]).astype(np.int32)
        s = -(-len(seq) // SEQ_BLOCK) * SEQ_BLOCK
        big = -(-max_new // 64) * 64
        tokens = np.zeros(s, np.int32)
        tokens[: len(seq)] = seq
        at = np.zeros(big, np.int32)
        at[:m] = np.arange(n - 1, n - 1 + m)
        tk = np.zeros(big, np.int32)
        tk[:m] = toks
        lg = logits(spec, root, jnp.asarray(tokens), jnp.asarray(at))
        out.append(np.asarray(_gap_of(lg, jnp.asarray(tk)))[:m])
    return out
