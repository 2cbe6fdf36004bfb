"""Plain float32 reference of the served dense decoder, for the output
check.

It imports nothing of the program and takes nothing the program made.
It builds the weights itself from the seed, by the initialisation the
program documents (``models/``): every matrix is ``N(0, 1) * 0.02``
drawn in float32 and stored in bfloat16, norm scales are 1, and the
keys split as

    root -> (embed, stack); embed -> (embedding, unembedding);
    stack -> one key per layer -> (attention, ffn);
    attention -> (wq, wk, wv, wo); ffn -> (gate, up, down).

The forward pass follows the published description: pre-norm RMSNorm,
rotary embeddings (rotate-half, ``rope_theta`` of the configuration),
causal grouped-query attention scaled by ``1/sqrt(head_dim)``, SwiGLU.
Every product is float32 at ``Precision.HIGHEST``. Layers run one at a
time in a scan, so only one layer's weights exist at once.

``gaps`` returns, for each served token, how far its reference logit
lies below the reference's best logit at that position.
"""
from __future__ import annotations

import functools
from typing import List, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
SEQ_BLOCK = 512      # sequences are padded to a multiple of this
Q_CHUNK = 512        # queries scored at once


class Spec(NamedTuple):
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    eps: float
    theta: float
    tied: bool

    @classmethod
    def from_hf(cls, hf: dict) -> "Spec":
        heads = hf["num_attention_heads"]
        return cls(
            layers=hf["num_hidden_layers"], d=hf["hidden_size"],
            heads=heads, kv_heads=hf["num_key_value_heads"],
            head_dim=hf.get("head_dim") or hf["hidden_size"] // heads,
            d_ff=hf["intermediate_size"], vocab=hf["vocab_size"],
            eps=float(hf["rms_norm_eps"]), theta=float(hf["rope_theta"]),
            tied=bool(hf["tie_word_embeddings"]))


def _w(key, shape):
    """A stored weight: float32 normal * 0.02, rounded to bfloat16."""
    return (jax.random.normal(key, shape, jnp.float32) * 0.02
            ).astype(jnp.bfloat16).astype(jnp.float32)


def _mm(eq, a, b):
    return jnp.einsum(eq, a, b, precision=HI)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, theta):
    """x: (s, h, hd); rotate-half with position = row index."""
    s, _, hd = x.shape
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v):
    """Causal GQA. q: (s, hq, hd); k, v: (s, hkv, hd)."""
    s, hq, hd = q.shape
    scale = 1.0 / np.sqrt(hd)
    hkv = k.shape[1]
    assert s % Q_CHUNK == 0, s
    qg = q.reshape(s // Q_CHUNK, Q_CHUNK, hkv, hq // hkv, hd)
    cols = jnp.arange(s)

    def chunk(args):
        i, qc = args
        sc = _mm("qkgd,tkd->kgqt", qc, k) * scale
        rows = i * Q_CHUNK + jnp.arange(Q_CHUNK)
        sc = jnp.where(cols[None, :] <= rows[:, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return _mm("kgqt,tkd->qkgd", p, v)

    out = jax.lax.map(chunk, (jnp.arange(s // Q_CHUNK), qg))
    return out.reshape(s, hq, hd)


def _layer(spec: Spec, x, key):
    ka, kf = jax.random.split(key)
    kq, kk, kv, ko = jax.random.split(ka, 4)
    d, hq, hkv, hd = spec.d, spec.heads, spec.kv_heads, spec.head_dim
    h = _rms(x, spec.eps)
    q = _mm("sd,dhk->shk", h, _w(kq, (d, hq, hd)))
    k = _mm("sd,dhk->shk", h, _w(kk, (d, hkv, hd)))
    v = _mm("sd,dhk->shk", h, _w(kv, (d, hkv, hd)))
    o = _attention(_rope(q, spec.theta), _rope(k, spec.theta), v)
    x = x + _mm("shk,hkd->sd", o, _w(ko, (hq, hd, d)))
    h = _rms(x, spec.eps)
    f = spec.d_ff
    kg, ku, kd = jax.random.split(kf, 3)
    g = _mm("sd,df->sf", h, _w(kg, (d, f)))
    u = _mm("sd,df->sf", h, _w(ku, (d, f)))
    x = x + _mm("sf,fd->sd", jax.nn.silu(g) * u, _w(kd, (f, d)))
    return x, None


@functools.partial(jax.jit, static_argnums=(0,))
def _logits(spec: Spec, root, tokens, at):
    """tokens: (S,) padded; at: (M,) positions. Returns (M, vocab)."""
    k_embed, k_stack = jax.random.split(root)
    k_emb, k_out = jax.random.split(k_embed)
    emb = _w(k_emb, (spec.vocab, spec.d))
    x = emb[tokens]
    x, _ = jax.lax.scan(functools.partial(_layer, spec), x,
                        jax.random.split(k_stack, spec.layers))
    h = _rms(x[at], spec.eps)
    out = emb.T if spec.tied else _w(k_out, (spec.d, spec.vocab))
    return _mm("md,dv->mv", h, out)


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
        logits = _logits(spec, root, jnp.asarray(tokens), jnp.asarray(at))
        out.append(np.asarray(_gap_of(logits, jnp.asarray(tk)))[:m])
    return out
