"""Serving-path spans and counters (``repro.obs.serving``) and the named
scopes of the model, at smoke widths on the CPU."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import ServeConfig, get_config, smoke_config
from repro.core.cluster import tpu_v5e_pod
from repro.models import model as lm
from repro.obs import serving as obs
from repro.obs import validate_chrome_trace
from repro.runtime import ClusterRuntime, LMServingWorkload, ScalePolicy
from repro.serving.engine import ServingEngine

REQUESTS, NEW_TOKENS = 6, 5


def drive(record: bool):
    """Serve six prompts through ClusterRuntime -> LMServingWorkload on
    a fresh engine; returns (outputs by rid, decode calls, recorder)."""
    cfg = smoke_config(get_config("internlm2-1.8b"))
    eng = ServingEngine(cfg, ServeConfig(max_seq_len=64))
    eng.init_random(0)
    calls = [0]
    decode = eng.decode_fn

    def counted(*a):
        calls[0] += 1
        return decode(*a)

    eng.decode_fn = counted
    wl = LMServingWorkload(eng, slots=4, max_new_tokens=NEW_TOKENS)
    rt = ClusterRuntime(tpu_v5e_pod(8), wl, policy=ScalePolicy(min_units=1),
                        unit_rate=0.25)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=n) for n in
               (9, 12, 12, 7, 9, 12)]
    rec = obs.start() if record else None
    try:
        for p in prompts:
            rt.submit(p)
        tel = rt.run(max_ticks=200)
    finally:
        obs.stop()
    outs = {r.rid: list(r.output) for r in tel.responses}
    return outs, calls[0], rec


@pytest.fixture(scope="module")
def off(request):
    entered = [0]
    real = jax.profiler.TraceAnnotation

    class Counting(real):
        def __enter__(self):
            entered[0] += 1
            return super().__enter__()

    jax.profiler.TraceAnnotation = Counting
    try:
        outs, calls, _ = drive(record=False)
    finally:
        jax.profiler.TraceAnnotation = real
    return outs, calls, entered[0]


@pytest.fixture(scope="module")
def on():
    return drive(record=True)


def test_recorder_off_records_nothing(off):
    outs, calls, entered = off
    assert obs.RECORDER is None
    assert len(outs) == REQUESTS and calls > 0
    assert entered == 0


def test_one_admit_span_per_request(on):
    outs, _, rec = on
    admits = rec.named("repro.batcher.admit")
    assert sorted(s.args["rid"] for s in admits) == sorted(outs)
    for s in admits:
        assert s.args["prompt_len"] in (7, 9, 12)
        assert s.args["queued_ns"] >= 0
        assert 0 <= s.args["slot"] < 4
        assert s.t1_ns >= s.t0_ns


def test_one_step_span_per_decode_step(on):
    _, calls, rec = on
    steps = rec.named("repro.batcher.step")
    assert sum(1 for s in steps if s.args["live"]) == calls
    assert len(rec.named("repro.engine.decode")) == calls
    assert len(rec.named("repro.batcher.sample")) == calls
    for s in steps:
        assert s.args["syncs"] >= (1 if s.args["live"] else 0)


def test_spans_nest_runtime_batcher_engine(on):
    _, _, rec = on
    sp = rec.spans

    def chain(i):
        out = []
        while i is not None:
            out.append(sp[i].name)
            i = sp[i].parent
        return out

    for i, s in enumerate(sp):
        if s.name == "repro.engine.prefill":
            assert chain(i)[1:] == ["repro.batcher.admit",
                                    "repro.batcher.step",
                                    "repro.runtime.tick"]
        elif s.name == "repro.engine.decode":
            assert chain(i)[1:] == ["repro.batcher.step",
                                    "repro.runtime.tick"]
        elif s.name in ("repro.runtime.gate", "repro.runtime.account"):
            assert chain(i)[1:] == ["repro.runtime.tick"]
        if s.parent is not None:
            p = sp[s.parent]
            assert p.t0_ns <= s.t0_ns and s.t1_ns <= p.t1_ns
    assert all(s.name.startswith("repro.") for s in sp)


def test_gate_counter_every_tick(on):
    _, _, rec = on
    gate = [c for c in rec.counters if c.name == "repro.gate"]
    assert len(gate) == len(rec.named("repro.runtime.tick"))
    for c in gate:
        assert set(c.values) == {"rate", "desired", "granted", "active",
                                 "hedged", "queued"}


def test_compiles_recorded_while_on(on):
    _, _, rec = on
    comp = rec.named("repro.compile")
    assert rec.compiles == len(comp) > 0
    assert all(s.parent is not None and s.args["duration_s"] >= 0
               for s in comp)
    before = rec.compiles
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(5)).block_until_ready()
    assert rec.compiles == before     # the listener left with stop()


def test_tokens_unchanged_by_recording(off, on):
    assert on[0] == off[0]
    assert all(len(v) == NEW_TOKENS for v in on[0].values())


def test_request_latencies_from_spans(on):
    outs, _, rec = on
    ttft, gaps = obs.request_latencies(rec)
    assert sorted(ttft) == sorted(outs)
    assert len(gaps) == sum(len(v) - 1 for v in outs.values())
    assert min(ttft.values()) > 0 and min(gaps) > 0


def test_saved_trace_is_valid_chrome_json(on, tmp_path):
    _, _, rec = on
    path = tmp_path / "spans.json"
    rec.save(str(path))
    with open(path) as f:
        trace = json.load(f)
    assert validate_chrome_trace(trace) == []
    names = {e["name"] for e in trace["traceEvents"]}
    assert {"repro.runtime.tick", "repro.batcher.admit", "repro.gate",
            "repro.engine.decode"} <= names


def test_decode_step_carries_named_scopes():
    cfg = smoke_config(get_config("internlm2-1.8b"))
    eng = ServingEngine(cfg, ServeConfig(max_seq_len=64))
    params = lm.param_shapes(cfg)
    caches = jax.eval_shape(lambda: lm.init_caches(cfg, 2, eng.max_len))
    text = eng.decode_fn.lower(
        params, jax.ShapeDtypeStruct((2, 1), jnp.int32), caches,
        jax.ShapeDtypeStruct((2,), jnp.int32)).compile().as_text()
    for scope in ("embed", "norm", "attn", "attn_core", "kv_write", "mlp",
                  "lm_head"):
        assert f"/{scope}/" in text, scope


def test_decode_span_counts_kv_blocks():
    """``kv_blocks``: each slot's KV blocks up to the one holding its new
    position (what the decode kernel's index map fetches per layer);
    ``kv_blocks_all``: every block of every slot."""
    cfg = smoke_config(get_config("internlm2-1.8b"))
    eng = ServingEngine(cfg, ServeConfig(max_seq_len=600))
    assert eng.max_len == 3 * 256
    eng.init_random(0)
    positions = np.array([0, 255, 256, 700])
    caches = lm.init_caches(cfg, len(positions), eng.max_len)
    with obs.recording() as rec:
        eng.decode(np.zeros(len(positions), np.int64), caches, positions)
    (span,) = rec.named("repro.engine.decode")
    assert span.args["kv_blocks"] == 1 + 1 + 2 + 3
    assert span.args["kv_blocks_all"] == 4 * 3


def test_serve_report_reads_the_spans(tmp_path):
    from repro.launch.serve import serve

    cfg = smoke_config(get_config("internlm2-1.8b"))
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, size=8) for _ in range(3)]
    path = tmp_path / "serve.json"
    report, outs = serve(cfg, prompts, max_new_tokens=4, slots=2,
                         trace_out=str(path))
    assert report["served"] == 3
    assert 0 < report["ttft_p50_s"] <= report["ttft_p95_s"]
    assert 0 < report["itl_p50_s"] <= report["itl_p95_s"]
    assert "tokens_per_s" not in report
    assert "p99_latency_ticks" not in report["telemetry"]
    with open(path) as f:
        assert validate_chrome_trace(json.load(f)) == []
    assert obs.RECORDER is None


def test_gate_counter_per_tenant_on_a_shared_pool():
    from repro.core.cluster import soc_cluster
    from repro.runtime import DLServingWorkload, MultiTenantRuntime, Tenant

    rt = MultiTenantRuntime(soc_cluster(), [
        Tenant(name, DLServingWorkload.from_point("resnet-50", "fp32",
                                                  "soc-gpu"))
        for name in ("a", "b")])
    with obs.recording() as rec:
        rt.play_traces({"a": [100.0] * 3, "b": [50.0] * 3}, dt_s=1.0,
                       drain=False)
    ticks = len(rec.named("repro.runtime.tick"))
    assert ticks == 3
    for name in ("a", "b"):
        assert sum(c.name == f"repro.gate/{name}" for c in rec.counters) \
            == ticks
