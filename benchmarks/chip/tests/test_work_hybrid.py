"""The hybrid family's work counts against numbers worked by hand at
granite-4.0-h-small's published widths and the file's cut."""
import json

import pytest

import work
import work_hybrid
from conftest import CHIP


@pytest.fixture(scope="module")
def m():
    with open(CHIP / "configs" / "granite-4.0-h-small.json") as f:
        return work_hybrid.Dims.from_conf(json.load(f))


def test_the_cut(m):
    assert (m.mamba_layers, m.attn_layers, m.held, m.experts) == (9, 1, 9, 72)
    assert (m.d_inner, m.conv_dim) == (8192, 8448)


def test_parameters(m):
    # in-projection 4096 x (2 x 8192 + 2 x 128 + 128), out 8192 x 4096
    assert work_hybrid.mamba_matmul_params(m) == 68_681_728 + 33_554_432
    assert work_hybrid.attn_matmul_params(m) == 41_943_040
    assert 9 * work_hybrid.expert_params(m) == 84_934_656
    assert work_hybrid.shared_params(m) == 18_874_368


def test_bytes_held(m):
    """The program's parameters at the cut take 4,835,610,624 bytes."""
    assert work_hybrid.weight_bytes(m) + work_hybrid.expert_bytes(m) == (
        4_835_610_624)
    assert work_hybrid.expert_bytes(m) == 10 * 84_934_656 * 2
    # 9 x 128 x 64 x 128 x 4 B; 9 x 3 x 8448 x 2 B; 1 x 2 x 8 x 128 x 2 B
    assert work_hybrid.ssd_state_bytes(m) == 37_748_736
    assert work_hybrid.conv_state_bytes(m) == 456_192
    assert work_hybrid.kv_bytes_per_token(m) == 4_096


def test_expected_experts(m):
    # one token uses 10 x 9 / 72 of the held experts; many use them all
    assert work_hybrid.expected_experts_used(m, 1) == pytest.approx(1.25)
    assert work_hybrid.expected_experts_used(m, 0) == 0
    assert work_hybrid.expected_experts_used(m, 400) == pytest.approx(9)


def test_decode_counts(m):
    live = [1000, 2000]
    state = 2 * 2 * (37_748_736 + 456_192)
    experts = 10 * work_hybrid.expected_experts_used(m, 2) * 9_437_184 * 2
    assert work_hybrid.decode_bytes(m, live) == pytest.approx(
        3_136_917_504 + experts + state + 3000 * 4096)
    attn = 1 * 4 * 32 * 128 * 3000
    assert work_hybrid.decode_flops(m, live) == pytest.approx(
        2 * work_hybrid.token_flops(m) + attn)
    # the held experts a token uses, in expectation: 1.25 of 9.4 M each
    moe = 2 * (4096 * 72 + 18_874_368 + 1.25 * 9_437_184)
    mamba = (2 * 102_236_160 + 2 * 4 * 8448 + 5 * 128 * 64 * 128)
    assert work_hybrid.token_flops(m) == pytest.approx(
        9 * mamba + 2 * 41_943_040 + 10 * moe + 2 * 4096 * 100_352)


def test_ssd_scan_counts(m):
    # 300 steps: a chunk of 256 and one of 44, each the causal half of
    # two c x c products (n + p = 192) and two state products
    per_head = (256 * 257 * 192 + 4 * 256 * 128 * 64
                + 44 * 45 * 192 + 4 * 44 * 128 * 64)
    assert work_hybrid.ssd_scan_flops(m, 300) == 9 * 128 * per_head
    per_layer = (300 * 128 * 64 * 2 * 2 + 300 * 128 * 4 + 2 * 300 * 128 * 2
                 + 128 * 64 * 128 * 4)
    assert work_hybrid.ssd_scan_bytes(m, 300) == 9 * per_layer
    # the scan of a short prompt is bound by its bytes (the final state
    # is 4 MB a layer), of a long one by its FLOPs
    p = work.peaks("TPU v5 lite")
    for s, bound in [(300, "bytes"), (2000, "flops")]:
        f, b = (work_hybrid.ssd_scan_flops(m, s),
                work_hybrid.ssd_scan_bytes(m, s))
        want = f / p["flops"] if bound == "flops" else (
            b / p["hbm_bytes_per_s"])
        assert work.roofline_s(f, b, p) == pytest.approx(want)
