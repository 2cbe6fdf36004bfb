"""Per-arch smoke tests: reduced config of the same family, one forward /
train step on CPU, asserting output shapes + no NaNs; decode-vs-forward
consistency in fp32."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import get_config, smoke_config
from repro.configs import ASSIGNED_ARCHS
from repro.models import model as lm


def _batch(cfg, b=2, s=32):
    batch = {
        "tokens": jnp.ones((b, s), jnp.int32),
        "labels": jnp.ones((b, s), jnp.int32),
        "mask": jnp.ones((b, s), jnp.float32),
    }
    if cfg.frontend_tokens:
        batch["vision_embeds"] = jnp.zeros(
            (b, cfg.frontend_tokens, cfg.frontend_dim or cfg.d_model),
            jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_smoke_train_step(arch):
    cfg = smoke_config(get_config(arch))
    params = lm.init_params(cfg, jax.random.key(0))
    batch = _batch(cfg)
    loss, metrics = lm.loss_fn(params, cfg, batch)
    assert np.isfinite(float(loss)), arch
    logits, _, _ = lm.forward(params, cfg, batch, mode="train")
    s_total = batch["tokens"].shape[1] + cfg.frontend_tokens
    assert logits.shape == (2, s_total, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits, np.float32)).all()


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_smoke_grad_step_updates_params(arch):
    cfg = smoke_config(get_config(arch))
    params = lm.init_params(cfg, jax.random.key(0))
    batch = _batch(cfg)
    grads = jax.grad(lambda p: lm.loss_fn(p, cfg, batch)[0])(params)
    gnorm = np.sqrt(sum(float(jnp.sum(jnp.square(g.astype(jnp.float32))))
                        for g in jax.tree.leaves(grads)))
    assert np.isfinite(gnorm) and gnorm > 0, arch


def _decode_cases():
    """The original cases (scanned layers, one position for the batch)
    keep their ids; a dense, a MoE and a hybrid attention + Mamba2 stack
    add the unrolled layers and slots at different positions. The hybrid
    stack takes 8 layers so that its period of 4 repeats in the scan."""
    cases = [pytest.param(a, None, True, False, id=a)
             for a in ["internlm2-1.8b", "granite-moe-1b-a400m",
                       "mamba2-130m", "jamba-1.5-large-398b",
                       "musicgen-large", "internvl2-1b"]]
    for arch, layers in [("internlm2-1.8b", None),
                         ("granite-moe-1b-a400m", None),
                         ("jamba-1.5-large-398b", 8)]:
        for scan in (True, False):
            for per_slot in (False, True):
                if scan and not per_slot and layers is None:
                    continue
                tag = "-".join(
                    [arch] + ([f"{layers}l"] if layers else [])
                    + ["scan" if scan else "unrolled"]
                    + (["per_slot"] if per_slot else []))
                cases.append(pytest.param(arch, layers, scan, per_slot,
                                          id=tag))
    return cases


@pytest.mark.parametrize("arch,layers,scan,per_slot", _decode_cases())
def test_decode_matches_forward_fp32(arch, layers, scan, per_slot):
    """prefill + decode(1) must equal the full forward at the decoded
    position, and the caches decode returns must equal those a prefill of
    one more token builds, at every position of every layer."""
    cfg = smoke_config(get_config(arch)).replace(dtype="float32")
    if layers:
        cfg = cfg.replace(num_layers=layers, layer_pattern=tuple(
            cfg.layer_pattern) * (layers // cfg.num_layers))
    if cfg.moe is not None:
        # capacity dropping legitimately depends on sequence length; use a
        # drop-free capacity so the equivalence is exact.
        import dataclasses
        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe,
                                                  capacity_factor=16.0))
    params = lm.init_params(cfg, jax.random.key(1))
    b, s = 2, 16
    lens = [s, s - 5] if per_slot else [s, s]
    toks = jax.random.randint(jax.random.key(2), (b, s + 1), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks}
    ve = None
    ft = cfg.frontend_tokens
    if ft:
        ve = jnp.asarray(np.random.default_rng(0).standard_normal(
            (b, ft, cfg.frontend_dim or cfg.d_model)), jnp.float32)
        batch["vision_embeds"] = ve
    full, _, _ = jax.jit(lambda p, bt: lm.forward(
        p, cfg, bt, mode="train", scan=scan))(params, batch)
    max_len = s + ft + 8
    prefill = jax.jit(lambda p, bt: lm.prefill(p, cfg, bt, max_len=max_len,
                                               scan=scan))

    def prefill_slots(extra):
        """The batch prefilled on its first s + extra tokens in one call;
        with per-slot positions, each slot prefilled alone on its first
        lens[i] + extra tokens and the caches joined along the batch."""
        if not per_slot:
            pre = {"tokens": toks[:, :s + extra]}
            if ve is not None:
                pre["vision_embeds"] = ve
            return prefill(params, pre)
        logits, caches = [], []
        for i, n in enumerate(lens):
            pre = {"tokens": toks[i:i + 1, :n + extra]}
            if ve is not None:
                pre["vision_embeds"] = ve[i:i + 1]
            lg, c = prefill(params, pre)
            logits.append(lg)
            caches.append(c)
        return (jnp.concatenate(logits),
                jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=1),
                             *caches))

    lg_pre, caches = prefill_slots(0)
    last = jnp.stack([toks[i, n] for i, n in enumerate(lens)])[:, None]
    pos = jnp.asarray(lens, jnp.int32) + ft
    lg_dec, dec_caches = jax.jit(lambda p, t, c, ps: lm.decode_step(
        p, cfg, t, c, ps, scan=scan))(params, last, caches, pos)
    rows = np.arange(b)
    at = np.asarray(lens)
    np.testing.assert_allclose(np.asarray(lg_pre),
                               np.asarray(full[rows, at - 1 + ft]),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(lg_dec),
                               np.asarray(full[rows, at + ft]),
                               rtol=1e-4, atol=1e-4)
    _, want = prefill_slots(1)
    assert jax.tree.structure(dec_caches) == jax.tree.structure(want)
    for got, ref in zip(jax.tree.leaves(dec_caches), jax.tree.leaves(want)):
        assert got.shape == ref.shape and got.dtype == ref.dtype
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)


def test_scan_equals_unrolled():
    cfg = smoke_config(get_config("jamba-1.5-large-398b")).replace(
        dtype="float32", num_layers=4)
    params = lm.init_params(cfg, jax.random.key(0))
    batch = _batch(cfg)
    l_scan, _, _ = lm.forward(params, cfg, batch, mode="train", scan=True)
    l_unr, _, _ = lm.forward(params, cfg, batch, mode="train", scan=False)
    np.testing.assert_allclose(np.asarray(l_scan), np.asarray(l_unr),
                               rtol=1e-5, atol=1e-5)


def test_remat_preserves_loss():
    cfg = smoke_config(get_config("internlm2-1.8b")).replace(dtype="float32")
    params = lm.init_params(cfg, jax.random.key(0))
    batch = _batch(cfg)
    l0, _ = lm.loss_fn(params, cfg, batch, remat="none")
    l1, _ = lm.loss_fn(params, cfg, batch, remat="full")
    l2, _ = lm.loss_fn(params, cfg, batch, remat="dots")
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6)
    np.testing.assert_allclose(float(l0), float(l2), rtol=1e-6)


def test_param_count_matches_headline():
    """Analytic param counts should match the arch ids' headline sizes."""
    expect = {
        "granite-moe-1b-a400m": (1.0e9, 2.0e9),
        "stablelm-12b": (11e9, 13e9),
        "phi3-medium-14b": (13e9, 16e9),
        "qwen2-72b": (70e9, 76e9),
        "internlm2-1.8b": (1.5e9, 2.1e9),
        "mamba2-130m": (0.1e9, 0.16e9),
        "jamba-1.5-large-398b": (380e9, 410e9),
    }
    for arch, (lo, hi) in expect.items():
        n = get_config(arch).num_params
        assert lo <= n <= hi, (arch, n)


def test_active_params_moe():
    g = get_config("granite-moe-1b-a400m")
    assert g.num_active_params < 0.6e9  # "a400m" + embeddings
    l4 = get_config("llama4-maverick-400b-a17b")
    assert l4.num_active_params < 0.05 * l4.num_params
