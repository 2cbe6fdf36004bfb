"""``kernels.ops`` chooses the Pallas kernels from the backend, and its TPU
branch pads sequences the kernels cannot tile (kernels in interpret mode,
on the CPU)."""
import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd_scan import ssd_scan


@pytest.fixture
def backend(monkeypatch):
    def fake(name):
        monkeypatch.setattr(jax, "default_backend", lambda: name)
    return fake


def _ops_calls(rng):
    """(ops function, args, kwargs, Pallas attribute of ops) per kernel."""
    f32 = jnp.float32
    x = jnp.asarray(rng.standard_normal((4, 32)), f32)
    q4 = jnp.asarray(rng.standard_normal((1, 8, 2, 16)), f32)
    q3 = jnp.asarray(rng.standard_normal((1, 2, 16)), f32)
    kv = jnp.asarray(rng.standard_normal((1, 8, 2, 16)), f32)
    # head-major decode caches: one layer, and three stacked
    cache = jnp.asarray(rng.standard_normal((1, 2, 8, 16)), f32)
    stacked = jnp.asarray(rng.standard_normal((3, 1, 2, 8, 16)), f32)
    length = jnp.array([5], jnp.int32)
    xs = jnp.asarray(rng.standard_normal((1, 8, 2, 4)), f32)
    dt = jnp.full((1, 8, 2), 0.1, f32)
    bc = jnp.asarray(rng.standard_normal((1, 8, 4)), f32)
    xq = jnp.ones((4, 8), jnp.int8)
    return {
        "rmsnorm": (ops.rmsnorm, (x, jnp.ones((32,), f32)), {},
                    "_pl_rmsnorm"),
        "attention": (ops.attention, (q4, kv, kv), {}, "_pl_flash"),
        "decode_attention": (ops.decode_attention,
                             (q3, cache, cache, length), {}, "_pl_decode"),
        "decode_attention_stacked": (
            ops.decode_attention, (q3, stacked, stacked, length, 1), {},
            "_pl_decode"),
        "int8_matmul": (ops.int8_matmul,
                        (xq, jnp.ones((4,), f32), xq.T, jnp.ones((4,), f32)),
                        {}, "_pl_int8"),
        "ssd": (ops.ssd, (xs, dt, -jnp.ones((2,), f32), bc, bc,
                          jnp.ones((2,), f32)), {"chunk": 8}, "_pl_ssd"),
    }


OPS = ["rmsnorm", "attention", "decode_attention",
       "decode_attention_stacked", "int8_matmul", "ssd"]
# The floating-point arguments of each call, which a gradient can take.
FLOAT_ARGS = {"rmsnorm": (0, 1), "attention": (0, 1, 2),
              "decode_attention": (0, 1, 2),
              "decode_attention_stacked": (0, 1, 2), "int8_matmul": (1, 3),
              "ssd": (0, 1, 2, 3, 4, 5)}


@pytest.mark.parametrize("name", OPS)
@pytest.mark.parametrize("platform", ["tpu", "cpu"])
def test_dispatch_follows_backend(name, platform, backend, monkeypatch, rng):
    fn, args, kwargs, pallas = _ops_calls(rng)[name]
    calls = []
    real = getattr(ops, pallas)

    def spy(*a, **kw):
        calls.append(name)
        return real(*a, **{**kw, "interpret": True})

    monkeypatch.setattr(ops, pallas, spy)
    backend(platform)
    assert ops.on_tpu() == (platform == "tpu")
    out = fn(*args, **kwargs)
    assert calls == ([name] if platform == "tpu" else [])
    assert all(np.isfinite(np.asarray(o)).all()
               for o in jax.tree.leaves(out))


@pytest.mark.parametrize("name", OPS)
def test_grad_through_kernel_is_reference_grad(name, backend, monkeypatch,
                                               rng):
    """A ``pallas_call`` cannot be transposed; on the TPU branch the
    gradient is the oracle's, so training works there too."""
    fn, args, kwargs, pallas = _ops_calls(rng)[name]
    monkeypatch.setattr(ops, pallas, functools.partial(getattr(ops, pallas),
                                                       interpret=True))

    def loss(*a):
        return sum(jnp.sum(o.astype(jnp.float32))
                   for o in jax.tree.leaves(fn(*a, **kwargs)))

    grad = jax.grad(loss, argnums=FLOAT_ARGS[name])
    backend("tpu")
    got = grad(*args)
    backend("cpu")
    want = grad(*args)
    for g, w in zip(got, want):
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("devices,pallas", [(1, True), (4, False)])
def test_sharded_program_keeps_xla_paths(devices, pallas, backend,
                                         monkeypatch):
    """The kernels are not wrapped in ``shard_map``: under a mesh of more
    than one device the TPU keeps the XLA paths and their layouts."""
    monkeypatch.setattr(ops, "active_mesh",
                        lambda: types.SimpleNamespace(size=devices))
    backend("tpu")
    assert ops.on_tpu() is pallas


def test_masked_attention_stays_on_reference(backend, monkeypatch, rng):
    """The ``kv_len`` branch is off the serving path and uses the oracle
    on the TPU too."""
    monkeypatch.setattr(ops, "_pl_flash", None)
    backend("tpu")
    q = jnp.asarray(rng.standard_normal((1, 8, 2, 16)), jnp.float32)
    out = ops.attention(q, q, q, kv_len=jnp.array([5]))
    want = ref.attention_ref(q, q, q, kv_len=jnp.array([5]))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


@pytest.mark.parametrize("s", [1, 37, 200, 300])
def test_prefill_pads_unaligned_prompt(s, backend, monkeypatch, rng):
    monkeypatch.setattr(ops, "_pl_flash",
                        functools.partial(flash_attention, interpret=True))
    backend("tpu")
    b, hq, hkv, d = 1, 4, 2, 32
    q = jnp.asarray(rng.standard_normal((b, s, hq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, hkv, d)), jnp.float32)
    out = ops.attention(q, k, v)
    assert out.shape == q.shape
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.attention_ref(q, k, v)),
                               atol=2e-6, rtol=2e-6)


def test_ssd_pads_unaligned_sequence(backend, monkeypatch, rng):
    monkeypatch.setattr(ops, "_pl_ssd",
                        functools.partial(ssd_scan, interpret=True))
    backend("tpu")
    b, s, h, p, n = 1, 50, 2, 8, 8
    f32 = jnp.float32
    x = jnp.asarray(rng.standard_normal((b, s, h, p)), f32)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, (b, s, h)), f32)
    A = -jnp.asarray(rng.uniform(0.5, 2.0, (h,)), f32)
    B = jnp.asarray(rng.standard_normal((b, s, n)), f32)
    C = jnp.asarray(rng.standard_normal((b, s, n)), f32)
    D = jnp.ones((h,), f32)
    y, state = ops.ssd(x, dt, A, B, C, D, chunk=16)
    y_ref, state_ref = ref.ssd_ref(x, dt, A, B, C, D)
    assert y.shape == x.shape
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(state), np.asarray(state_ref),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("n,want", [(40, 40), (256, 256), (257, 512),
                                    (382, 512), (512, 512), (513, 768)])
def test_cache_len_tiles_decode_blocks(n, want):
    assert ops.cache_len(n) == want


def test_kernels_name_untileable_shapes():
    q = jnp.zeros((1, 200, 2, 16))
    with pytest.raises(ValueError, match="sq=200"):
        flash_attention(q, q, q, interpret=True)
    from repro.kernels.decode_attention import decode_attention
    k = jnp.zeros((1, 2, 300, 16))
    with pytest.raises(ValueError, match="cache length 300"):
        decode_attention(jnp.zeros((1, 2, 16)), k, k, jnp.array([3]),
                         interpret=True)
