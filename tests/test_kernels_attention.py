"""Pallas flash/decode attention vs the pure-jnp oracle: shape/dtype sweeps
(interpret=True executes the kernel body on CPU)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import (decode_attention,
                                             kv_block_index)
from repro.kernels.flash_attention import flash_attention

SHAPES = [
    # (b, sq, hq, hkv, d)
    (1, 128, 4, 4, 64),     # MHA
    (2, 256, 8, 2, 64),     # GQA 4:1
    (2, 128, 4, 1, 128),    # MQA
    (1, 512, 2, 2, 32),     # long-ish
]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_oracle(shape, dtype, causal, rng):
    b, sq, hq, hkv, d = shape
    q = jnp.asarray(rng.standard_normal((b, sq, hq, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, sq, hkv, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, sq, hkv, d)), dtype)
    out_ref = ref.attention_ref(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                          interpret=True)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(out_ref, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("block_q,block_k", [(32, 64), (128, 32), (64, 128)])
def test_flash_attention_block_shapes(block_q, block_k, rng):
    b, sq, hq, hkv, d = 1, 256, 2, 1, 64
    q = jnp.asarray(rng.standard_normal((b, sq, hq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, sq, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, sq, hkv, d)), jnp.float32)
    out_ref = ref.attention_ref(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=block_q,
                          block_k=block_k, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_ref),
                               atol=2e-6, rtol=2e-6)


# (skv, hq, hkv, d): g = hq // hkv of 1, 2 and 4, heads of 64 and 128
DECODE_SHAPES = [(256, 4, 4, 64), (512, 8, 2, 64), (256, 4, 1, 128),
                 (512, 8, 4, 128), (384, 4, 2, 64)]
DECODE_BLOCK = 128


def _decode_cache(rng, shape, dtype, layers):
    """q (b=4, hq, d) and a head-major K and V, stacked over ``layers``
    (None: one layer's (b, hkv, skv, d))."""
    skv, hq, hkv, d = shape
    lead = (4,) if layers is None else (layers, 4)
    q = jnp.asarray(rng.standard_normal((4, hq, d)), dtype)
    k = jnp.asarray(rng.standard_normal(lead + (hkv, skv, d)), dtype)
    v = jnp.asarray(rng.standard_normal(lead + (hkv, skv, d)), dtype)
    return q, k, v


@pytest.mark.parametrize("shape", DECODE_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("layers", [None, 3], ids=["layer", "stacked"])
def test_decode_attention_matches_oracle(shape, dtype, layers, rng):
    """Slots of length 1, one block, one block and one, and the whole
    cache; a stacked cache is read at layer 2."""
    skv = shape[0]
    q, k, v = _decode_cache(rng, shape, dtype, layers)
    layer = None if layers is None else 2
    length = jnp.array([1, DECODE_BLOCK, DECODE_BLOCK + 1, skv], jnp.int32)
    out_ref = ref.decode_attention_ref(q, k, v, length, layer)
    out = decode_attention(q, k, v, length, layer, block_k=DECODE_BLOCK,
                           interpret=True)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(out_ref, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("layers", [None, 3], ids=["layer", "stacked"])
def test_decode_attention_respects_length(layers, rng):
    """Entries beyond `length` must not influence the output: finite
    garbage in each slot's last live block past its length, NaN in whole
    dead blocks and in every other layer of a stacked cache."""
    shape = (512, 8, 2, 64)
    q, k, v = _decode_cache(rng, shape, jnp.float32, layers)
    layer = None if layers is None else 1
    length = np.array([100, 200, 1, 512])
    out1 = decode_attention(q, k, v, jnp.asarray(length), layer,
                            block_k=DECODE_BLOCK, interpret=True)
    pos = np.arange(shape[0])
    live_end = -(-length // DECODE_BLOCK) * DECODE_BLOCK
    tail = (pos >= length[:, None]) & (pos < live_end[:, None])
    dead = pos >= live_end[:, None]
    mask = np.zeros(k.shape, bool)
    garbage = np.zeros(k.shape, np.float32)
    at = (slice(None),) if layers is None else (layer,)
    mask[at] = (tail | dead)[:, None, :, None]
    garbage[at] = np.where(dead, np.nan, 999.0)[:, None, :, None]
    if layers is not None:
        others = np.arange(layers) != layer
        mask[others], garbage[others] = True, np.nan
    k2 = jnp.where(mask, garbage, k)
    v2 = jnp.where(mask, -garbage, v)
    out2 = decode_attention(q, k2, v2, jnp.asarray(length), layer,
                            block_k=DECODE_BLOCK, interpret=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2))


@pytest.mark.parametrize("block_k", [128, 256])
def test_decode_index_map_stops_at_last_live_block(block_k):
    """The K and V index map, called directly: each slot's own blocks up
    to its last live one, then that one again, never a block past it."""
    skv = 1024
    length = jnp.array([1, block_k, block_k + 1, skv, 2 * block_k - 1])
    layer = jnp.array([3])
    for bi, n in enumerate(np.asarray(length)):
        last = -(-n // block_k) - 1
        got = [kv_block_index(bi, ki, length, layer, block_k=block_k)
               for ki in range(skv // block_k)]
        assert all(tuple(map(int, g[:3])) == (3, bi, 0) and int(g[4]) == 0
                   for g in got)
        blocks = [int(g[3]) for g in got]
        assert blocks == [min(ki, last) for ki in range(skv // block_k)]
        assert max(blocks) == last
