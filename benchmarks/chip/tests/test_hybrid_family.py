"""The hybrid family serves granite-4.0-h-small's cell through
``harness.run`` at a size the CPU holds.

The configuration file keeps its keys and its cut, at tiny widths: the
registry's model is replaced by one of eight layers (attention at 1 and
5) and eight experts, and the family checks it against the file and
cuts it to the file's four layers and two experts from the third, as it
does on the chip. The look for a chip is the only part skipped. The
prompts are not multiples of the SSD chunk.
"""
import time

import jax
import jax.numpy as jnp
import pytest

import harness
from families import hybrid

SEED = 2**33 + 23
# A limit on the mean logit gap for this size, from CPU runs over seeds
# 2**33 + 23..27: sound runs read 4.95e-7 to 7.96e-7, the int8 control
# 1.70e-6 to 4.46e-6 (logits here are divided by 16, as published, and
# lie within about 0.02 of each other).
LIMIT = 1.2e-6


def tiny_cell():
    from repro.config import MambaConfig, MoEConfig, get_config

    c = harness.load_cell("granite-4.0-h-small.chat64")
    conf = c["config"]
    conf.update(
        hidden_size=256, num_attention_heads=4, num_key_value_heads=2,
        vocab_size=4096, mamba_n_heads=8, mamba_d_head=64, mamba_d_state=32,
        mamba_chunk_size=32, intermediate_size=128,
        shared_intermediate_size=192, num_experts_per_tok=3,
        num_hidden_layers=4, num_local_experts=2,
        layer_types=["mamba", "attention", "mamba", "mamba"])
    conf["deployment"].update(layers_published=8, experts_published=8,
                              expert_first=2)
    published = get_config(conf["registry"]).replace(
        num_layers=8, d_model=256, num_heads=4, num_kv_heads=2, head_dim=64,
        vocab_size=4096, max_seq_len=4096,
        layer_pattern=tuple("attn" if i in (1, 5) else "mamba"
                            for i in range(8)),
        moe=MoEConfig(num_experts=8, top_k=3, d_ff_expert=128,
                      d_ff_shared=192),
        mamba=MambaConfig(d_state=32, d_conv=4, expand=2, headdim=64,
                          chunk_size=32))
    mix = c["mix"]
    # a low knee makes the activation gate grant every slot at once
    mix.update(slots=4, max_seq_len=512, lead_in_s=1, trace_s=1,
               knee_rps=0.5)
    mix["arrivals"]["rate_per_s"] = 4.0
    mix["prompt"]["grid"] = [45, 77]
    mix["output"]["max"] = 48
    mix["check"].update(mean_logit_gap=LIMIT, tokens=200, requests=8,
                        min_tokens=100)
    return c, published


@pytest.fixture(scope="module")
def cell():
    return tiny_cell()


def run(cell, monkeypatch, patch=None, seed=SEED, serve=None):
    c, published = cell
    monkeypatch.setattr(hybrid, "registry", lambda conf: published)
    return harness.run(c, seed, 3.0, False, time.time(), patch=patch,
                       peak_kind="TPU v5 lite", serve=serve)


def test_the_family_checks_and_cuts_the_registry(cell, monkeypatch):
    c, published = cell
    monkeypatch.setattr(hybrid, "registry", lambda conf: published)
    cfg = hybrid.program_config(c["config"])
    assert cfg.layer_kinds() == ("mamba", "attn", "mamba", "mamba")
    assert (cfg.moe.expert_first, cfg.moe.held, cfg.moe.num_experts) == (
        2, 2, 8)
    wide = published.replace(d_model=512)
    monkeypatch.setattr(hybrid, "registry", lambda conf: wide)
    with pytest.raises(ValueError, match="hidden_size"):
        hybrid.program_config(c["config"])


def test_hybrid_cell_serves_correctly(cell, monkeypatch):
    seen = {}

    def record(srv):
        decode = srv.engine.decode_fn

        def g(p, t, caches, q):
            logits, new = decode(p, t, caches, q)
            seen["caches"] = new
            return logits, new
        srv.engine.decode_fn = g

    out = run(cell, monkeypatch, patch=record)
    assert out["correct"] and out["failed"] == 0, out["check"]
    assert out["readings"]["tokens_compared"] >= 100
    states = [c["ssd"] for c in seen["caches"] if "ssd" in c]
    assert states and any("k" in c for c in seen["caches"])


def state_unchanged(srv):
    """The decode step hands back the cache it was given: the Mamba state
    stops at the prompt."""
    decode = srv.engine.decode_fn

    def g(p, t, c, q):
        logits, _ = decode(p, t, jax.tree.map(jnp.copy, c), q)
        return logits, c
    srv.engine.decode_fn = g


def test_a_stale_state_is_caught(cell, monkeypatch):
    out = run(cell, monkeypatch, patch=state_unchanged)
    assert not out["correct"], out["check"]
