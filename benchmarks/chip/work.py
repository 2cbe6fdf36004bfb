"""Work the algorithm needs, counted from shapes, and the chip's peaks.

These counts are the yardstick for every roofline and MFU metric. They
count what the computation needs, not what today's kernels move: a
change that removes wasted bytes or FLOPs must lower the time and leave
these numbers alone.

Conventions (bf16 weights and caches, 2 bytes an element):

* a matmul of (m, k) by (k, n) is ``2*m*k*n`` FLOPs;
* causal attention over ``s`` positions scores each query against the
  keys at or before it: ``s*(s+1)/2`` pairs, each ``2*hd`` FLOPs for
  q.k and ``2*hd`` for p.v, per query head;
* decode attention needs, per live slot and layer, K and V of its live
  positions once per KV head, plus its q and its output.

Copied and corrected from ``benchmarks/bench_kernels.py``, whose
attention formula counts the full square.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Iterable

# Published peaks, keyed by ``jax.Device.device_kind``. A device that is
# not here is an error: no number is reported against a guessed peak.
PEAKS = {
    "TPU v5 lite": {
        "flops": 197e12,          # bf16 FLOP/s
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16 * 2**30,
        "source": "Google Cloud documentation, 'TPU v5e'",
    },
}

BYTES = 2   # bf16


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None


@dataclass(frozen=True)
class Dims:
    """The shapes that set the work of one decoder layer stack."""

    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int          # SwiGLU width
    vocab: int

    @classmethod
    def from_hf(cls, hf: dict) -> "Dims":
        heads = hf["num_attention_heads"]
        return cls(
            layers=hf["num_hidden_layers"], d=hf["hidden_size"],
            heads=heads, kv_heads=hf["num_key_value_heads"],
            head_dim=hf.get("head_dim") or hf["hidden_size"] // heads,
            d_ff=hf["intermediate_size"], vocab=hf["vocab_size"])

    @property
    def kv_bytes_per_token(self) -> int:
        return self.layers * 2 * self.kv_heads * self.head_dim * BYTES


def layer_matmul_params(m: Dims) -> int:
    """Weights one token multiplies by in one layer."""
    attn = 2 * m.d * m.heads * m.head_dim + 2 * m.d * m.kv_heads * m.head_dim
    return attn + 3 * m.d * m.d_ff


def token_matmul_flops(m: Dims) -> int:
    """Matmul FLOPs of one token through every layer, without the head."""
    return 2 * m.layers * layer_matmul_params(m)


def head_flops(m: Dims) -> int:
    """The output projection of one position."""
    return 2 * m.d * m.vocab


def causal_attention_flops(m: Dims, s: int) -> int:
    """Flash attention over ``s`` positions in every layer, causal half
    with the diagonal."""
    return m.layers * 4 * m.heads * m.head_dim * (s * (s + 1) // 2)


def prefill_flops(m: Dims, s: int) -> int:
    """One prompt of ``s`` tokens: every layer at every position, causal
    attention, and the head at the last position only (the only logits
    the step returns)."""
    return (s * token_matmul_flops(m) + causal_attention_flops(m, s)
            + head_flops(m))


def decode_attention_flops(m: Dims, lengths: Iterable[int]) -> int:
    return sum(m.layers * 4 * m.heads * m.head_dim * n for n in lengths)


def decode_attention_bytes(m: Dims, lengths: Iterable[int]) -> int:
    """K and V of each live slot's positions, once per KV head, plus
    its q and output, in every layer."""
    qo = 2 * m.heads * m.head_dim * BYTES
    return sum(m.layers * (2 * n * m.kv_heads * m.head_dim * BYTES + qo)
               for n in lengths)


def decode_step_flops(m: Dims, lengths: Iterable[int]) -> int:
    """One decode step for the live slots at ``lengths`` (positions
    attended, the new one included)."""
    lengths = list(lengths)
    return (len(lengths) * (token_matmul_flops(m) + head_flops(m))
            + decode_attention_flops(m, lengths))


def roofline_s(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peak["flops"], nbytes / peak["hbm_bytes_per_s"])
