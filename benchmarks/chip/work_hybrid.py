"""Work the hybrid family's computation needs, counted from shapes: the
yardstick of its MFU and roofline metrics.

A hybrid stack here is Mamba-2 mixers and attention layers, each layer
followed by a routed MoE block of which this chip holds a share, plus a
shared expert; the vocabulary is tied. As in ``work.py``, these count
what the computation needs, not what today's kernels move:

* a matmul of (m, k) by (k, n) is ``2*m*k*n`` FLOPs;
* a token's routed experts are counted by their expected number here
  under uniform routing: ``top_k * held / experts`` of them;
* a decode step reads every weight once, but a held expert only if some
  live token routes to it: ``held * (1 - ((E - k) / E) ** live)``
  experts' weights, in expectation under uniform routing;
* a decode step reads and writes back the SSD and conv state of each
  live slot, and reads K and V of each live slot's positions once per
  KV head;
* the SSD scan of a prompt is the chunked algorithm at the published
  chunk: per head and chunk of ``c`` steps, the causal half of the two
  ``c x c`` products (``C B^T`` and its product with ``x``) and the two
  state products (``C h_in`` and the state update), once; it reads
  ``x``, ``dt``, ``B`` and ``C`` and writes ``y`` and the final state
  once.

Weights are bfloat16 but the router, norm scales and the Mamba mixer's
per-head and per-channel vectors (``A_log``, ``dt_bias``, ``D``, the
gated norm's scale), which are float32. The SSD state is float32, the
conv window and K and V bfloat16.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Iterable, Tuple

BF16, F32 = 2, 4


@dataclass(frozen=True)
class Dims:
    """The shapes that set the work of the hybrid stack held here."""

    kinds: Tuple[str, ...]     # per layer: "mamba" or "attention"
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    m_heads: int
    m_head_dim: int
    m_state: int
    m_conv: int
    chunk: int
    experts: int               # the router's outputs
    held: int                  # experts held here
    top_k: int
    d_expert: int
    d_shared: int

    @classmethod
    def from_conf(cls, conf: dict) -> "Dims":
        """From the top-level keys of a configuration file (the catalog's
        ``config``, with the cut) and its ``deployment``."""
        heads = conf["num_attention_heads"]
        return cls(
            kinds=tuple(conf["layer_types"]), d=conf["hidden_size"],
            heads=heads, kv_heads=conf["num_key_value_heads"],
            head_dim=conf.get("head_dim") or conf["hidden_size"] // heads,
            vocab=conf["vocab_size"], m_heads=conf["mamba_n_heads"],
            m_head_dim=conf["mamba_d_head"], m_state=conf["mamba_d_state"],
            m_conv=conf["mamba_d_conv"], chunk=conf["mamba_chunk_size"],
            experts=conf["deployment"]["experts_published"],
            held=conf["num_local_experts"],
            top_k=conf["num_experts_per_tok"],
            d_expert=conf["intermediate_size"],
            d_shared=conf["shared_intermediate_size"])

    @property
    def mamba_layers(self) -> int:
        return sum(k == "mamba" for k in self.kinds)

    @property
    def attn_layers(self) -> int:
        return sum(k == "attention" for k in self.kinds)

    @property
    def d_inner(self) -> int:
        return self.m_heads * self.m_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.m_state


# -- parameters ----------------------------------------------------------------
def mamba_matmul_params(m: Dims) -> int:
    """In-projection (z, x, B, C, dt) and out-projection of one mixer."""
    return m.d * (2 * m.d_inner + 2 * m.m_state + m.m_heads) + m.d_inner * m.d


def attn_matmul_params(m: Dims) -> int:
    return 2 * m.d * m.heads * m.head_dim + 2 * m.d * m.kv_heads * m.head_dim


def expert_params(m: Dims) -> int:
    return 3 * m.d * m.d_expert


def shared_params(m: Dims) -> int:
    return 3 * m.d * m.d_shared


def weight_bytes(m: Dims) -> int:
    """Every weight held here but the routed experts, once."""
    mamba = (BF16 * (mamba_matmul_params(m) + m.m_conv * m.conv_dim
                     + m.conv_dim)
             + F32 * (3 * m.m_heads + m.d_inner))
    per_layer = (BF16 * shared_params(m) + F32 * m.d * m.experts   # router
                 + F32 * 2 * m.d)                                  # norms
    return (BF16 * m.vocab * m.d + F32 * m.d
            + m.mamba_layers * mamba
            + m.attn_layers * BF16 * attn_matmul_params(m)
            + len(m.kinds) * per_layer)


def expert_bytes(m: Dims) -> int:
    """The held experts of every layer."""
    return len(m.kinds) * m.held * BF16 * expert_params(m)


def expected_experts_used(m: Dims, live: int) -> float:
    """Held experts of one layer that some of ``live`` tokens route to,
    in expectation under uniform top-k routing."""
    return m.held * (1.0 - ((m.experts - m.top_k) / m.experts) ** live)


# -- state ---------------------------------------------------------------------
def ssd_state_bytes(m: Dims) -> int:
    """One slot's SSD state, every Mamba layer."""
    return m.mamba_layers * m.m_heads * m.m_head_dim * m.m_state * F32


def conv_state_bytes(m: Dims) -> int:
    return m.mamba_layers * (m.m_conv - 1) * m.conv_dim * BF16


def kv_bytes_per_token(m: Dims) -> int:
    return m.attn_layers * 2 * m.kv_heads * m.head_dim * BF16


# -- decode --------------------------------------------------------------------
def token_flops(m: Dims) -> float:
    """One decoded token through every layer and the head, without
    attention over the cache: matmuls, the conv, the SSD recurrence
    (``5*p*n`` a head: decay, outer product, add, and ``y = h C``), the
    router, the expected routed experts held here and the shared one."""
    mamba = (2 * mamba_matmul_params(m) + 2 * m.m_conv * m.conv_dim
             + 5 * m.m_heads * m.m_head_dim * m.m_state)
    moe = 2 * (m.d * m.experts + shared_params(m)
               + m.top_k * m.held / m.experts * expert_params(m))
    return (m.mamba_layers * mamba + m.attn_layers * 2 * attn_matmul_params(m)
            + len(m.kinds) * moe + 2 * m.d * m.vocab)


def decode_flops(m: Dims, lengths: Iterable[int]) -> float:
    """One decode step for the live slots at ``lengths`` (positions
    attended, the new one included)."""
    lengths = list(lengths)
    attn = sum(m.attn_layers * 4 * m.heads * m.head_dim * n for n in lengths)
    return len(lengths) * token_flops(m) + attn


def decode_bytes(m: Dims, lengths: Iterable[int]) -> float:
    """The bytes one decode step needs: the weights once, the held
    experts some live token uses, each live slot's state read and
    written back, and K and V of each live slot's positions."""
    lengths = list(lengths)
    experts = (len(m.kinds) * expected_experts_used(m, len(lengths))
               * BF16 * expert_params(m))
    state = 2 * len(lengths) * (ssd_state_bytes(m) + conv_state_bytes(m))
    return (weight_bytes(m) + experts + state
            + sum(lengths) * kv_bytes_per_token(m))


# -- the SSD scan of a prompt --------------------------------------------------
def ssd_scan_flops(m: Dims, s: int) -> int:
    """The chunked SSD of one prompt of ``s`` steps, every Mamba layer."""
    n, p = m.m_state, m.m_head_dim
    chunks = [m.chunk] * (s // m.chunk) + ([s % m.chunk] if s % m.chunk
                                           else [])
    per_head = sum(c * (c + 1) * (n + p) + 4 * c * n * p for c in chunks)
    return m.mamba_layers * m.m_heads * per_head


def ssd_scan_bytes(m: Dims, s: int) -> int:
    """x and y in bfloat16, dt in float32, B and C in bfloat16, the final
    state in float32; every Mamba layer."""
    h, p, n = m.m_heads, m.m_head_dim, m.m_state
    per_layer = (s * h * p * BF16 * 2 + s * h * F32 + 2 * s * n * BF16
                 + h * p * n * F32)
    return m.mamba_layers * per_layer
