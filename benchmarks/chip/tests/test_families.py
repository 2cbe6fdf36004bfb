"""A model family is a module of its own, named by the configuration.

The dense family gives, bit for bit, the gaps that ``reference.gaps``
gave on the same inputs before the harness asked a family for them
(``tests/data/dense_gaps.json``). A whole run through a stub family,
whose program is a hybrid of Mamba, attention and MoE layers, uses the
stub's program, dims, vocabulary and gaps and nothing of the dense
family's.
"""
import json
import sys
import time
import types

import numpy as np
import pytest

import harness
import readers
from conftest import CHIP

with open(CHIP / "tests" / "data" / "dense_gaps.json") as f:
    DENSE_CASES = json.load(f)["cases"]


@pytest.mark.parametrize("case", DENSE_CASES, ids=lambda c: c["name"])
def test_dense_gaps_match_the_recorded_reference(case):
    conf = {"family": "dense", "hf": case["hf"]}
    got = harness.family(conf).gaps(
        conf, case["seed32"], [np.asarray(p, np.int32) for p in case["prompts"]],
        case["served"], case["max_new"])
    want = [np.asarray(g, np.float32) for g in case["gaps"]]
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.dtype == np.float32
        assert np.array_equal(g, w)


STUB_VOCAB = 300      # below the program's 512: the traffic draws from it


def stub_family(seen: dict):
    """A family module whose every answer is recorded in ``seen``."""
    from repro.config import get_config, smoke_config

    fam = types.ModuleType("families.stub")
    cfg = smoke_config(get_config("jamba-1.5-large-398b"))
    dims = object()

    def program_config(conf):
        seen["program_config"] = conf
        return cfg

    def dims_of(conf):
        seen["dims"] = dims
        return dims

    def gaps(conf, seed32, prompts, served, max_new):
        seen["gaps"] = (prompts, served, max_new)
        return [np.zeros(len(s), np.float32) for s in served]

    fam.program_config, fam.dims, fam.gaps = program_config, dims_of, gaps
    fam.vocab = lambda conf: STUB_VOCAB
    return fam, cfg


def test_a_stub_family_serves_a_hybrid_model(monkeypatch):
    seen: dict = {}
    fam, cfg = stub_family(seen)
    monkeypatch.setitem(sys.modules, "families.stub", fam)
    c = harness.load_cell("internlm2-1.8b.chat")
    c["config"] = {"name": "stub-hybrid", "family": "stub"}
    c["per_layer"] = []
    mix = c["mix"]
    # a low knee makes the activation gate grant every slot at once; the
    # prompts are whole chunks of the smoke config's SSD (32)
    mix.update(slots=4, max_seq_len=512, lead_in_s=1, trace_s=1,
               knee_rps=0.5)
    mix["arrivals"]["rate_per_s"] = 4.0
    mix["prompt"]["grid"] = [96, 160]
    mix["output"]["max"] = 48
    mix["check"].update(tokens=200, requests=8, min_tokens=100)

    def read_all(metrics, ctx):
        seen["ctx_dims"] = ctx.dims
        return {}

    monkeypatch.setattr(readers, "read_all", read_all)

    def record(srv):
        seen["engine_cfg"] = srv.engine.cfg
        decode = srv.engine.decode_fn

        def g(p, t, caches, q):
            logits, new = decode(p, t, caches, q)
            seen["caches"] = new
            return logits, new
        srv.engine.decode_fn = g

    out = harness.run(c, 2**33 + 29, 3.0, True, time.time(), patch=record,
                      peak_kind="TPU v5 lite")

    assert seen["program_config"] is c["config"]
    assert seen["engine_cfg"] is cfg
    assert seen["ctx_dims"] is seen["dims"]
    prompts, served, max_new = seen["gaps"]
    assert max_new == 48
    assert max(int(p.max()) for p in prompts) < STUB_VOCAB
    assert out["readings"]["tokens_compared"] == sum(map(len, served)) >= 100
    assert out["correct"] and out["failed"] == 0, out["check"]
    # the served caches hold Mamba state beside attention's K and V
    assert set(cfg.layer_kinds()) == {"mamba", "attn"} and cfg.moe is not None
    states = [c["ssd"] for c in seen["caches"] if "ssd" in c]
    assert states and any("k" in c for c in seen["caches"])
    assert all(float(np.abs(np.asarray(s)).max()) > 0 for s in states)
