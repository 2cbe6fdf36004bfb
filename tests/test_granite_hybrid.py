"""granite-4.0-h-small at a tiny size: Mamba-2 and NoPE attention layers,
a routed MoE block with a shared expert in every layer, Granite's
factors, and a held share of the experts.

The served path (prefill, then decode through the cache) is compared on
logits with the plain float32 reference of the chip benchmark
(``benchmarks/chip/reference_hybrid.py``, which imports nothing of the
program), at seeded random weights. The expert layer is checked for its
shares and for dropping nothing; attention for ignoring positions.
"""
import dataclasses
import functools
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import ModelConfig, MoEConfig, get_config, smoke_config
from repro.kernels import ops, ref
from repro.models import attention as attn_mod
from repro.models import model as lm
from repro.models import moe as moe_mod
from repro.models.layers import mlp_apply
from repro.obs import serving as obs
from repro.serving.engine import ServingEngine, recurrent_state_bytes

sys.path.insert(0, str(Path(__file__).resolve().parents[1]
                       / "benchmarks" / "chip"))
import reference_hybrid  # noqa: E402

ARCH = "granite-4.0-h-small"


def tiny(dtype="float32", first=1, held=2):
    """The smoke config of the published model, holding ``held`` of its 4
    experts from ``first``."""
    cfg = smoke_config(get_config(ARCH)).replace(dtype=dtype)
    return cfg.replace(moe=dataclasses.replace(
        cfg.moe, expert_first=first, experts_held=held))


def reference_spec(cfg):
    """The reference's spec of a program config, through the keys of the
    configuration file it reads on the chip."""
    m, moe = cfg.mamba, cfg.moe
    conf = {
        "layer_types": ["attention" if k == "attn" else "mamba"
                        for k in cfg.layer_kinds()],
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "head_dim": cfg.resolved_head_dim, "vocab_size": cfg.vocab_size,
        "rms_norm_eps": cfg.norm_eps, "tie_word_embeddings": True,
        "position_embedding_type": "nope",
        "mamba_n_heads": m.n_heads(cfg.d_model), "mamba_d_head": m.headdim,
        "mamba_d_state": m.d_state, "mamba_d_conv": m.d_conv,
        "mamba_n_groups": 1, "mamba_expand": m.expand,
        "num_local_experts": moe.held, "num_experts_per_tok": moe.top_k,
        "intermediate_size": moe.d_ff_expert,
        "shared_intermediate_size": moe.d_ff_shared,
        "embedding_multiplier": cfg.embedding_multiplier,
        "residual_multiplier": cfg.residual_multiplier,
        "attention_multiplier": cfg.attention_multiplier,
        "logits_scaling": cfg.logits_scaling,
        "deployment": {"experts_published": moe.num_experts,
                       "expert_first": moe.expert_first},
    }
    return reference_hybrid.Spec.from_conf(conf)._replace(dtype=cfg.dtype)


def test_registry_holds_the_published_model():
    cfg = get_config(ARCH)
    kinds = cfg.layer_kinds()
    assert len(kinds) == 40 and kinds.count("attn") == 4
    assert [i for i, k in enumerate(kinds) if k == "attn"] == [5, 15, 25, 35]
    assert cfg.mamba.n_heads(cfg.d_model) == 128
    assert cfg.moe.num_experts == 72 and cfg.moe.held == 72
    assert not cfg.rope and cfg.logits_scaling == 16.0
    # "32B-A9B"; the analytic count leaves out nothing but biases
    assert 31e9 < cfg.num_params < 33e9


def test_smoke_config_keeps_the_hybrid():
    cut = get_config(ARCH).replace(moe=dataclasses.replace(
        get_config(ARCH).moe, experts_held=9))
    cfg = smoke_config(cut)
    assert set(cfg.layer_kinds()) == {"mamba", "attn"}
    assert not cfg.rope and cfg.moe.d_ff_shared and cfg.moe.cut
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.attention_multiplier, cfg.logits_scaling) == (
        12.0, 0.22, 0.0078125, 16.0)


# ---------------------------------------------------------------------------
# The served path against the reference.
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("lens", [(45, 70), (33, 31)],
                         ids=["45-70", "33-31"])
def test_prefill_then_decode_matches_reference(lens):
    """Two slots at their own positions, each prefilled alone and joined
    into the batched cache as the batcher does, then decoded greedily;
    every logit of every step against the reference's full forward pass
    over the same tokens. The lengths are not multiples of the SSD chunk
    (32)."""
    cfg = tiny()
    seed, steps = 1234, 12
    params = lm.init_params(cfg, jax.random.key(seed))
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in lens]
    max_len = 128
    outs, caches = [], []
    for p in prompts:
        lg, c = lm.prefill(params, cfg, {"tokens": jnp.asarray(p[None])},
                           max_len=max_len)
        outs.append([np.asarray(lg[0])])
        caches.append(c)
    caches = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=1), *caches)
    toks = [[int(np.argmax(o[0]))] for o in outs]
    pos = np.asarray(lens, np.int32)
    for _ in range(steps):
        lg, caches = lm.decode_step(
            params, cfg, jnp.asarray([[t[-1]] for t in toks]), caches,
            jnp.asarray(pos))
        pos = pos + 1
        for i in range(len(lens)):
            outs[i].append(np.asarray(lg[i]))
            toks[i].append(int(np.argmax(outs[i][-1])))
    spec = reference_spec(cfg)
    for p, o, t in zip(prompts, outs, toks):
        seq = np.concatenate([p, t[:-1]]).astype(np.int32)
        tokens = np.zeros(reference_hybrid.SEQ_BLOCK, np.int32)
        tokens[: len(seq)] = seq
        at = np.arange(len(p) - 1, len(seq), dtype=np.int32)
        want = np.asarray(reference_hybrid.logits(
            spec, jax.random.key(seed), jnp.asarray(tokens),
            jnp.asarray(at)))
        # Both sides are float32 on the same weights; they differ only
        # in the order of sums (chunked SSD against the recurrence, the
        # dispatch's scatter-add against a sum over experts), which moves
        # logits of size ~0.1 by ~1e-8. 2e-6 leaves that a hundred times
        # over and is below the gap between the two best logits of most
        # positions, so a wrong factor, position or expert fails it.
        np.testing.assert_allclose(np.stack(o), want, rtol=0, atol=2e-6)


def test_kernel_decode_skips_finished_slots(monkeypatch):
    """The decode step on the kernels' TPU branch (interpret mode) with
    the middle of three slots finished: the live slots' logits match the
    oracles' step over every slot, the finished slot's SSD state is left
    bit for bit, and its logits stay finite."""
    cfg = tiny()
    params = lm.init_params(cfg, jax.random.key(5))
    rng = np.random.default_rng(3)
    caches = []
    for n in (21, 40, 33):
        _, c = lm.prefill(params, cfg, {"tokens": jnp.asarray(
            rng.integers(0, cfg.vocab_size, (1, n)), jnp.int32)},
            max_len=64)
        caches.append(c)
    caches = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=1), *caches)
    toks = jnp.asarray([[3], [4], [5]], jnp.int32)
    pos = jnp.asarray([21, 40, 33], jnp.int32)
    want, _ = lm.decode_step(params, cfg, toks, caches, pos)

    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    for name in ("_pl_rmsnorm", "_pl_decode", "_pl_ssd_decode"):
        monkeypatch.setattr(ops, name, functools.partial(
            getattr(ops, name), interpret=True))
    live = np.array([True, False, True])
    got, new = lm.decode_step(params, cfg, toks, caches, pos,
                              live=jnp.asarray(live))
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               rtol=0, atol=1e-5)
    assert np.isfinite(np.asarray(got)).all()
    for old, upd in zip(caches, new):
        if "ssd" in old:
            np.testing.assert_array_equal(np.asarray(upd["ssd"])[:, 1],
                                          np.asarray(old["ssd"])[:, 1])


# ---------------------------------------------------------------------------
# The expert layer.
# ---------------------------------------------------------------------------
def moe_cfg(first=0, held=0, e=8, k=3):
    return ModelConfig(
        name="t", family="moe", num_layers=1, d_model=32, num_heads=2,
        num_kv_heads=2, d_ff=0, vocab_size=64, dtype="float32",
        moe=MoEConfig(num_experts=e, top_k=k, d_ff_expert=16,
                      d_ff_shared=24, expert_first=first,
                      experts_held=held))


def dense_layer(params, cfg, x):
    """Every expert of the whole layer on every token, weighted by the
    softmax of the token's top-k logits, plus the shared expert."""
    moe = cfg.moe
    xt = x.reshape(-1, x.shape[-1])
    top_l, top_i = jax.lax.top_k(xt @ params["router"], moe.top_k)
    w = jax.nn.softmax(top_l, axis=-1)
    out = mlp_apply(params["shared"], x).reshape(xt.shape)
    for e in range(moe.num_experts):
        h = (jax.nn.silu(xt @ params["w_gate"][e])
             * (xt @ params["w_up"][e]))
        out = out + (h @ params["w_down"][e]) * jnp.sum(
            jnp.where(top_i == e, w, 0.0), axis=-1)[:, None]
    return out.reshape(x.shape)


@pytest.mark.parametrize("mode,s", [("prefill", 13), ("decode", 1)])
def test_held_shares_add_up_to_the_uncut_layer(mode, s):
    """Four shares of two experts each: each holds the uncut layer's own
    experts, and their outputs, with the shared expert counted once, add
    up to the uncut layer."""
    key = jax.random.key(3)
    x = jax.random.normal(jax.random.key(4), (3, s, 32), jnp.float32)
    whole = moe_cfg()
    p_whole = moe_mod.moe_init(key, whole)
    want = dense_layer(p_whole, whole, x)
    got_whole, _ = moe_mod.moe_apply(p_whole, whole, x, mode=mode)
    np.testing.assert_allclose(np.asarray(got_whole), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    total = -3 * mlp_apply(p_whole["shared"], x)
    for first in range(0, 8, 2):
        cfg = moe_cfg(first, 2)
        p = moe_mod.moe_init(key, cfg)
        for name in ("w_gate", "w_up", "w_down"):
            np.testing.assert_array_equal(p[name],
                                          p_whole[name][first: first + 2])
        y, _ = moe_mod.moe_apply(p, cfg, x, mode=mode)
        total = total + y
    # float32 sums in another order: 1e-5 of outputs of size ~1
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("first,held", [(0, 0), (4, 2)],
                         ids=["uncut", "share"])
def test_no_token_is_dropped_when_every_token_routes_to_one_expert(
        first, held):
    """The router sends all 64 tokens to expert 5 first: a capacity of
    1.25 x 64 x 3 / 8 = 24 rows an expert would drop 40 of them (and
    does, in the training dispatch); serving computes every one."""
    cfg = moe_cfg(first, held)
    p = moe_mod.moe_init(jax.random.key(5), cfg)
    p_whole = moe_mod.moe_init(jax.random.key(5), moe_cfg())
    router = jnp.zeros((32, 8)).at[:, 5].set(1.0)
    p["router"], p_whole["router"] = router, router
    x = jnp.abs(jax.random.normal(jax.random.key(6), (1, 64, 32)))
    out, _ = moe_mod.moe_apply(p, cfg, x, mode="prefill")
    # the uncut layer's expert 5 part, which every share holding it gives
    top_l, top_i = jax.lax.top_k(x[0] @ router, 3)
    assert bool(jnp.all(top_i[:, 0] == 5))
    w5 = jax.nn.softmax(top_l, axis=-1)[:, 0]
    h = (jax.nn.silu(x[0] @ p_whole["w_gate"][5])
         * (x[0] @ p_whole["w_up"][5]))
    want = (h @ p_whole["w_down"][5]) * w5[:, None] + mlp_apply(
        p_whole["shared"], x)[0]
    for e in range(8):
        if e != 5 and first <= e < first + (held or 8):
            hit = jnp.sum(jnp.where(top_i == e, jax.nn.softmax(
                top_l, axis=-1), 0.0), axis=-1)
            he = (jax.nn.silu(x[0] @ p_whole["w_gate"][e])
                  * (x[0] @ p_whole["w_up"][e]))
            want = want + (he @ p_whole["w_down"][e]) * hit[:, None]
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    if not held:
        dropped, _ = moe_mod.moe_apply(p, cfg, x, mode="train")
        assert not np.allclose(np.asarray(dropped), np.asarray(out),
                               atol=1e-3)


# ---------------------------------------------------------------------------
# Attention without positions.
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("rope", [False, True], ids=["nope", "rope"])
def test_nope_attention_ignores_a_shift_of_positions(rope):
    """The 20 tokens before a decoded one, each moved 7 positions on
    (cyclically): attention without positions gives the decoded token the
    same output, since it sees the same keys and values; with rotary
    embeddings, which rotate each key by its position, it does not."""
    cfg = tiny().replace(rope=rope)
    p = attn_mod.attn_init(jax.random.key(8), cfg)
    n, max_len = 20, 32
    # inputs large enough that the scores, at Granite's scale of 1/128,
    # are far from uniform
    hist = 30 * jax.random.normal(jax.random.key(9), (1, n, cfg.d_model))
    x = 30 * jax.random.normal(jax.random.key(10), (1, 1, cfg.d_model))

    def decoded(h):
        _, cache = attn_mod.attn_apply(p, cfg, h, mode="prefill",
                                       max_len=max_len)
        stacked = jax.tree.map(lambda t: t[None], cache)
        y, _ = attn_mod.attn_apply(p, cfg, x, mode="decode", cache=stacked,
                                   pos=jnp.asarray([n], jnp.int32), layer=0)
        return np.asarray(y)

    same = np.allclose(decoded(hist), decoded(jnp.roll(hist, 7, axis=1)),
                       rtol=1e-5, atol=1e-5)
    assert same != rope


# ---------------------------------------------------------------------------
# Around the model.
# ---------------------------------------------------------------------------
def test_ssd_takes_any_length_off_the_tpu(rng):
    """``ops.ssd`` pads to the chunk before it picks a backend."""
    b, s, h, p, n = 1, 45, 2, 8, 16
    x = jnp.asarray(rng.standard_normal((b, s, h, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.3, (b, s, h)), jnp.float32)
    A = jnp.asarray(-rng.uniform(0.3, 2.0, (h,)), jnp.float32)
    B = jnp.asarray(rng.standard_normal((b, s, n)), jnp.float32)
    C = jnp.asarray(rng.standard_normal((b, s, n)), jnp.float32)
    D = jnp.asarray(rng.standard_normal((h,)), jnp.float32)
    y, st = ops.ssd(x, dt, A, B, C, D, chunk=32)
    y_ref, st_ref = ref.ssd_ref(x, dt, A, B, C, D)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(st_ref),
                               rtol=2e-4, atol=2e-4)


def test_engine_spans_carry_the_recurrent_state():
    cfg = tiny(dtype="bfloat16")
    m = cfg.mamba
    layers = cfg.layer_kinds().count("mamba")
    per_slot = layers * (m.n_heads(cfg.d_model) * m.headdim * m.d_state * 4
                         + (m.d_conv - 1) * (m.d_inner(cfg.d_model)
                                             + 2 * m.d_state) * 2)
    assert recurrent_state_bytes(cfg) == per_slot
    assert recurrent_state_bytes(smoke_config(
        get_config("internlm2-1.8b"))) == 0
    eng = ServingEngine(cfg)
    eng.init_random(0)
    with obs.recording() as rec:
        _, cache = eng.prefill(np.arange(9, dtype=np.int32))
        cache3 = jax.tree.map(lambda t: jnp.concatenate([t] * 3, 1), cache)
        _, cache3 = eng.decode(np.zeros(3, np.int64), cache3, np.full(3, 9))
        eng.decode(np.zeros(3, np.int64), cache3, np.full(3, 10),
                   np.array([True, False, True]))
    got = [(s.name, s.args.get("state_bytes"), s.args.get("state_bytes_all"))
           for s in rec.spans if s.name.startswith("repro.engine")]
    assert got == [("repro.engine.prefill", per_slot, None),
                   ("repro.engine.decode", 3 * per_slot, 3 * per_slot),
                   ("repro.engine.decode", 2 * per_slot, 3 * per_slot)]
