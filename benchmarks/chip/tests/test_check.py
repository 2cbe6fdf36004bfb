"""The output check fails a broken timed path.

Each test drives a whole run of the chat cell, cut to a size the CPU
holds (the look for a chip is the only part skipped), with
one fault planted in the timed path, and expects ``correct`` to come
out false. The sound run comes out true. The cell's one chip has no
exchange between chips to leave out.
"""
import time

import jax
import jax.numpy as jnp
import pytest

import harness

SEED = 2**33 + 17
# A limit on the mean logit gap for this size, set from readings on the
# CPU over 5 seeds: sound runs read 0.000075-0.000237, the int8 control
# 0.000353-0.00134 (test_control). At this size they lie closer than at
# the cell's own (0.0025 against 0.019 on the chip).
LIMIT = 0.0003


def tiny_cell():
    """The chat cell at 4 layers of width 512 (heads of 64), a vocabulary
    of 8192, 4 slots of 512 and short outputs: small enough for the CPU,
    wide enough that the int8 control reads apart from sound runs."""
    from repro.config import get_config

    c = harness.load_cell("internlm2-1.8b.chat")
    sm = get_config("internlm2-1.8b").replace(
        num_layers=4, d_model=512, num_heads=8, num_kv_heads=4, head_dim=64,
        d_ff=2048, vocab_size=8192)
    c["config"]["hf"].update(
        num_hidden_layers=sm.num_layers, hidden_size=sm.d_model,
        num_attention_heads=sm.num_heads, num_key_value_heads=sm.num_kv_heads,
        head_dim=sm.head_dim, intermediate_size=sm.d_ff,
        vocab_size=sm.vocab_size)
    mix = c["mix"]
    # a low knee makes the activation gate grant every slot at once
    mix.update(slots=4, max_seq_len=512, lead_in_s=1, trace_s=1,
               knee_rps=0.5)
    mix["arrivals"]["rate_per_s"] = 4.0
    mix["prompt"]["grid"] = [157, 229]
    mix["output"]["max"] = 48
    mix["check"].update(mean_logit_gap=LIMIT, tokens=200, requests=8,
                        min_tokens=100)
    return c, sm


@pytest.fixture(scope="module")
def cell():
    return tiny_cell()


def run(cell, patch=None, monkeypatch=None, seed=SEED, serve=None):
    c, sm = cell
    hf = c["config"]["hf"]
    monkeypatch.setattr(
        harness.family(c["config"]), "program_config",
        lambda conf: sm.replace(rope_theta=float(hf["rope_theta"]),
                                norm_eps=float(hf["rms_norm_eps"])))
    return harness.run(c, seed, 3.0, False, time.time(), patch=patch,
                       peak_kind="TPU v5 lite", serve=serve)


def wrap_decode(srv, f):
    srv.engine.decode_fn = f(srv.engine.decode_fn)


def altered_token(srv):
    """Every 16th step, each slot's token is its least likely one."""
    def f(fn):
        calls = [0]

        def g(p, t, c, q):
            logits, new = fn(p, t, c, q)
            calls[0] += 1
            return (-logits if calls[0] % 16 == 0 else logits), new
        return g
    wrap_decode(srv, f)


def state_unchanged(srv):
    """The decode step hands back the cache it was given."""
    def f(fn):
        def g(p, t, c, q):
            logits, _ = fn(p, t, jax.tree.map(jnp.copy, c), q)
            return logits, c
        return g
    wrap_decode(srv, f)


def half_batch(srv):
    """The first half of the slots is left out of the step."""
    def f(fn):
        def g(p, t, c, q):
            logits, new = fn(p, t, c, q)
            half = logits.shape[0] // 2
            return logits.at[:half].set(0.0), new
        return g
    wrap_decode(srv, f)


def test_sound_run_is_correct(cell, monkeypatch):
    out = run(cell, monkeypatch=monkeypatch)
    assert out["correct"], out["check"]
    assert list(out)[-1] == "check"


@pytest.mark.parametrize("fault", [altered_token, state_unchanged,
                                   half_batch])
def test_fault_is_caught(cell, monkeypatch, fault):
    out = run(cell, patch=fault, monkeypatch=monkeypatch)
    assert not out["correct"], out["check"]
