"""The main path's kernels compiled for a TPU v5e that is described, not
attached, at internlm2-1.8b widths (the SSD scan at mamba2-130m widths).

Nothing runs: each test lowers and compiles for one chip of a ``v5e:2x2``
topology and asserts that the Pallas kernel is in the program
(``tpu_custom_call``); the decode step also reads the compiler's memory
analysis. The topology and every sharding are built inside the fixtures
below, never at import time, so that under several pytest
workers only the worker given this file loads the TPU compiler.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.config import TrainConfig, get_config
from repro.kernels import ops
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.ssd_scan import ssd_scan
from repro.models import model as lm
from repro.training.optimizer import init_opt_state
from repro.training.train_loop import make_train_step

ARCH = "internlm2-1.8b"


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def topo(no_persistent_cache):
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def spec(one_chip):
    def make(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("s", [256, 2048])
def test_flash_attention_compiles(spec, s):
    cfg = get_config(ARCH)
    hq, hkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    text = _compiled_text(flash_attention, spec((1, s, hq, d)),
                          spec((1, s, hkv, d)), spec((1, s, hkv, d)))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("arch", [ARCH, "granite-4.0-h-small"])
def test_decode_attention_compiles(spec, arch):
    """On a stacked head-major cache at g = 2 (internlm2) and g = 4
    (granite): the kernel keeps its name and no transpose is made."""
    cfg = get_config(arch)
    hq, hkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    skv = ops.cache_len(440)
    assert skv == 512
    cache = spec((3, 4, hkv, skv, d))
    text = _compiled_text(decode_attention, spec((4, hq, d)), cache, cache,
                          spec((4,), jnp.int32), spec((), jnp.int32))
    assert "tpu_custom_call" in text and "%decode_attention" in text
    assert "transpose(" not in text


@pytest.mark.parametrize("rows", [300, 4], ids=["prefill", "decode"])
def test_rmsnorm_compiles(spec, rows):
    d = get_config(ARCH).d_model
    text = _compiled_text(rmsnorm, spec((rows, d)), spec((d,), jnp.float32))
    assert "tpu_custom_call" in text


def test_ssd_scan_compiles(spec):
    cfg = get_config("mamba2-130m")
    m = cfg.mamba
    h, p, n, s = m.n_heads(cfg.d_model), m.headdim, m.d_state, 512
    f32 = jnp.float32

    def scan(x, dt, A, B, C, D):
        return ssd_scan(x, dt, A, B, C, D, chunk=m.chunk_size)

    text = _compiled_text(scan, spec((1, s, h, p)), spec((1, s, h), f32),
                          spec((h,), f32), spec((1, s, n)), spec((1, s, n)),
                          spec((h,), f32))
    assert "tpu_custom_call" in text


@pytest.fixture
def tpu_branch(monkeypatch):
    """Steer ``kernels.ops`` to its TPU branch, as on the chip."""
    monkeypatch.setattr(ops, "on_tpu", lambda: True)


def _placed(tree, one_chip):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)


@pytest.mark.parametrize("slots,positions", [(4, 342 + 32 + 8), (16, 3072)],
                         ids=["small", "chat"])
def test_decode_step_compiles(tpu_branch, one_chip, spec, slots, positions):
    """The decode step with its caches donated, as the engine jits it:
    the Pallas kernel is in the program, the whole cache aliases the
    output, and the kernel reads it in place: no transpose or copy under
    ``attn_core``, and temp below a quarter of one layer's K and V. At
    the chat cell's 16 x 3072 a copy of one layer's K and V would not fit
    the chip's fast memory and would show in temp."""
    cfg = get_config(ARCH)
    max_len = ops.cache_len(positions)
    params = _placed(lm.param_shapes(cfg), one_chip)
    caches = _placed(
        jax.eval_shape(lambda: lm.init_caches(cfg, slots, max_len)),
        one_chip)
    cache_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(caches))

    def step(params, tokens, caches, pos):
        return lm.decode_step(params, cfg, tokens, caches, pos)

    compiled = jax.jit(step, donate_argnums=(2,)).lower(
        params, spec((slots, 1), jnp.int32), caches,
        spec((slots,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    moved = [line for line in text.splitlines() if "attn_core" in line
             and re.search(r"= \S+ (transpose|copy)\(", line)]
    assert not moved, moved
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == cache_bytes
    assert mem.temp_size_in_bytes < cache_bytes / cfg.num_layers / 4


def test_prefill_compiles_at_unaligned_length(tpu_branch, one_chip, spec):
    cfg = get_config(ARCH)
    params = _placed(lm.param_shapes(cfg), one_chip)

    def prefill(params, tokens):
        return lm.prefill(params, cfg, {"tokens": tokens}, max_len=512)

    text = _compiled_text(prefill, params, spec((1, 342), jnp.int32))
    assert "tpu_custom_call" in text


def test_train_step_compiles(tpu_branch, one_chip, spec):
    """Reverse mode through the kernels (full width, depth cut to two
    layers, an unaligned sequence length)."""
    cfg = dataclasses.replace(get_config(ARCH), num_layers=2)
    tcfg = TrainConfig()
    params = lm.param_shapes(cfg)
    opt = _placed(jax.eval_shape(lambda p: init_opt_state(p, tcfg), params),
                  one_chip)
    batch = {"tokens": spec((1, 300), jnp.int32),
             "labels": spec((1, 300), jnp.int32),
             "mask": spec((1, 300), jnp.int32)}
    text = _compiled_text(make_train_step(cfg, tcfg),
                          _placed(params, one_chip), opt, batch)
    assert "tpu_custom_call" in text


# ---------------------------------------------------------------------------
# granite-4.0-h-small as the chip benchmark cuts it: one period of 9 Mamba
# layers and 1 attention layer, 9 of 72 experts held, at published widths.
# ---------------------------------------------------------------------------
def _granite_cut():
    cfg = get_config("granite-4.0-h-small")
    return cfg.replace(num_layers=10, layer_pattern=cfg.layer_kinds()[:10],
                       moe=dataclasses.replace(cfg.moe, experts_held=9))


def test_ssd_scan_compiles_at_granite_widths(spec):
    """128 heads of 64, state 128, chunk 256; the kernel keeps its name."""
    m = get_config("granite-4.0-h-small").mamba
    h, p, n, s = 128, m.headdim, m.d_state, 1280
    f32 = jnp.float32

    def scan(x, dt, A, B, C, D):
        return ssd_scan(x, dt, A, B, C, D, chunk=m.chunk_size)

    text = _compiled_text(scan, spec((1, s, h, p)), spec((1, s, h), f32),
                          spec((h,), f32), spec((1, s, n)), spec((1, s, n)),
                          spec((h,), f32))
    assert "%ssd_scan" in text


def test_granite_decode_step_compiles(tpu_branch, one_chip, spec):
    """The hybrid decode step with its caches (SSD and conv state, KV)
    donated: every cache aliases the output, and the held experts run as
    XLA's ragged dot, not as one dense product per expert."""
    cfg = _granite_cut()
    params = _placed(lm.param_shapes(cfg), one_chip)
    caches = _placed(jax.eval_shape(lambda: lm.init_caches(cfg, 4, 512)),
                     one_chip)
    cache_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(caches))

    def step(params, tokens, caches, pos):
        return lm.decode_step(params, cfg, tokens, caches, pos)

    compiled = jax.jit(step, donate_argnums=(2,)).lower(
        params, spec((4, 1), jnp.int32), caches,
        spec((4,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "ragged" in text
    assert compiled.memory_analysis().alias_size_in_bytes == cache_bytes


def test_granite_prefill_compiles_at_unaligned_length(tpu_branch, one_chip,
                                                      spec):
    """A prompt of 300 tokens: the SSD scan pads to its chunk and the
    expert layer's row buffer (300 x 9, rounded to whole tiles) stays a
    ragged dot."""
    cfg = _granite_cut()
    params = _placed(lm.param_shapes(cfg), one_chip)

    def prefill(params, tokens):
        return lm.prefill(params, cfg, {"tokens": tokens}, max_len=512)

    text = _compiled_text(prefill, params, spec((1, 300), jnp.int32))
    assert "%ssd_scan" in text and "ragged" in text
