"""Work counts against numbers worked by hand at the published widths."""
import json

import pytest

import work
from conftest import CHIP


def dims(name):
    with open(CHIP / "configs" / f"{name}.json") as f:
        return work.Dims.from_hf(json.load(f)["hf"])


def test_internlm2_counts():
    m = dims("internlm2-1.8b")
    # attention 2*2048*16*128 + 2*2048*8*128, SwiGLU 3*2048*8192
    assert work.layer_matmul_params(m) == 12_582_912 + 50_331_648
    assert m.kv_bytes_per_token == 98_304
    assert work.token_matmul_flops(m) == 3_019_898_880
    assert work.head_flops(m) == 379_060_224
    # one slot attending 1,000 positions: + 24 * 4 * 16 * 128 * 1000
    assert work.decode_step_flops(m, [1000]) == 3_595_567_104
    # K and V of 1,000 positions x 8 heads x 128 x 2 B, q and o, 24 layers
    assert work.decode_attention_bytes(m, [1000]) == 24 * (4_096_000 + 8_192)
    # 4 positions, causal: 10 pairs
    assert work.causal_attention_flops(m, 4) == 24 * 4 * 16 * 128 * 10
    # 8192 tokens, 8192*8193/2 causal pairs, the head once
    assert work.prefill_flops(m, 8192) == (
        8192 * 3_019_898_880 + 24 * 4 * 16 * 128 * 33_558_528 + 379_060_224)


def test_roofline_takes_the_larger_bound():
    p = work.peaks("TPU v5 lite")
    assert work.roofline_s(197e12, 0, p) == pytest.approx(1.0)
    assert work.roofline_s(0, 819e9, p) == pytest.approx(1.0)
    assert work.roofline_s(197e12, 2 * 819e9, p) == pytest.approx(2.0)


def test_unknown_device_is_an_error():
    with pytest.raises(ValueError, match="no published peaks"):
        work.peaks("TPU v9 imaginary")
