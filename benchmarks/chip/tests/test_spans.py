"""The reduction of the program's spans and scopes, and the readers of the
span metrics."""
import json
from types import SimpleNamespace

import pytest

import devtrace as d
import readers
import spans as s
from conftest import CHIP

DEV, HOST = "/device:TPU:0", "/host:CPU"
METRICS = [n for n, _ in s.SPAN_METRICS]


def op(name, t0, dur, scope=""):
    return [DEV, d.OPS_LINE, name, t0, dur, scope]


def span(name, t0, dur, **args):
    return [HOST, "python3", name, t0, dur, args]


def ctx_of(events):
    lo, hi = d.window(events)
    return SimpleNamespace(events=d.clip(events, lo, hi), lo_ns=lo, hi_ns=hi)


def read(name, ctx):
    return readers.reader(f"{name}.chat")(ctx)


# A window of 100 ns: a tick [0, 60) holding a gate [0, 5), a step
# [5, 55) with an admit [5, 25) (engine prefill [6, 10)), an engine
# decode [26, 30) and a sample [40, 50); the client from 60 on. The device
# runs [8, 20) and [28, 45) and [70, 80).
SYNTH = [
    [HOST, "python3", d.WINDOW_SPAN, 0, 100, None],
    span("repro.runtime.tick", 0, 60, tick=1),
    span("repro.runtime.gate", 0, 5),
    span("repro.batcher.step", 5, 50),
    span("repro.batcher.admit", 5, 20, rid=3, queued_ns=7_000_000),
    span("repro.engine.prefill", 6, 4),
    span("repro.engine.decode", 26, 4),
    span("repro.batcher.sample", 40, 10),
    [DEV, d.MODULES_LINE, "jit__prefill(1)", 8, 12, None],
    op("%fusion.1", 8, 12),
    [DEV, d.MODULES_LINE, "jit__decode(2)", 28, 17, None],
    op("%decode_attention.3", 28, 6, "jit(_decode)/while/body/attn/attn_core/pallas_call"),
    op("%copy.4", 34, 3, "jit(_decode)/while/body/attn/attn_core/transpose"),
    op("%scatter.5", 37, 2, "jit(_decode)/while/body/attn/kv_write/scatter"),
    op("%dynamic-update-slice.6", 39, 6, "jit(_decode)/while/body"),
    op("%fusion.7", 70, 10),
]


def test_innermost_labels_nested_spans():
    segs = s.innermost(s.host_spans(SYNTH), 0, 100)
    assert segs[0] == (0, 5, "repro.runtime.gate")
    assert (6, 10, "repro.engine.prefill") in segs
    assert (55, 60, "repro.runtime.tick") in segs
    assert segs[-1] == (60, 100, None)
    assert sum(b - a for a, b, _ in segs) == 100


def test_idle_by_layer_adds_up_to_idle():
    split = s.idle_by_layer(d.clip(SYNTH, 0, 100), 0, 100)
    # idle: [0, 8) [20, 28) [45, 70) [80, 100)
    assert split == {"runtime": 5 + 5, "batcher": 1 + 5 + 1 + 5 + 5,
                     "engine": 2 + 2, "client": 10 + 20}
    assert sum(split.values()) == pytest.approx(
        100 * d.idle_share(SYNTH, 0, 100))


def test_decode_scopes_by_innermost_scope():
    calls, scopes = s.decode_scopes(SYNTH)
    assert calls == 1
    assert scopes == {"attn_core": 9, "kv_write": 2, "unscoped": 6}


def test_readers_on_synthetic_window():
    ctx = ctx_of(SYNTH)
    assert read("queued_p90_ms", ctx) == pytest.approx(7.0)
    assert read("admit_share", ctx) == pytest.approx(20.0)
    assert read("idle_runtime_share", ctx) == pytest.approx(10.0)
    assert read("idle_batcher_share", ctx) == pytest.approx(17.0)
    assert read("idle_engine_share", ctx) == pytest.approx(4.0)
    assert read("decode_attn_core_ms", ctx) == pytest.approx(9e-6)


def test_readers_silent_without_the_program_spans():
    """A trace of a program without the recorder and scopes (the
    accepted fixture, in ``devtrace.load``'s five-element form)."""
    with open(CHIP / "tests" / "data" / "trace_v5e_decode.json") as f:
        ctx = ctx_of(json.load(f)["events"])
    assert {m: read(m, ctx) for m in METRICS} == dict.fromkeys(METRICS)


HLO = """\
HloModule jit__decode

%fused_computation.3 (param_0: bf16[4]) -> bf16[4] {
  %param_0 = bf16[4]{0} parameter(0)
  ROOT %sine.1 = bf16[4]{0} sine(%param_0), metadata={op_name="jit(_decode)/while/body/mlp/sin" stack_frame_id=3}
}

ENTRY %main.9 (p: bf16[4]) -> bf16[4] {
  %p = bf16[4]{0} parameter(0)
  %fusion.2 = bf16[4]{0} fusion(%p), kind=kLoop, calls=%fused_computation.3
  %decode_attention.3 = bf16[4]{0} custom-call(%fusion.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(_decode)/while/body/attn/attn_core/pallas_call"}
  ROOT %copy.4 = bf16[4]{0} copy(%decode_attention.3)
}
"""


def test_op_names_from_hlo_text():
    names = s.op_names(HLO)
    assert names["fusion.2"] == "jit(_decode)/while/body/mlp/sin"
    assert names["decode_attention.3"].endswith("/attn/attn_core/pallas_call")
    assert "copy.4" not in names
    assert s.scope_label(names["fusion.2"]) == "mlp"
    assert s.scope_label("") == "unscoped"


@pytest.fixture(scope="module")
def recorded():
    """Three ticks of a --trace 1 run of the chat cell on a TPU v5e, the
    first admitting a request of 1069 tokens."""
    with open(CHIP / "tests" / "data" / "trace_v5e_spans.json") as f:
        return ctx_of(json.load(f)["events"])


def test_recorded_idle_split(recorded):
    ev, lo, hi = recorded.events, recorded.lo_ns, recorded.hi_ns
    split = s.idle_by_layer(ev, lo, hi)
    assert split == {"client": 82_950, "runtime": 498_696,
                     "engine": 8_973_776, "batcher": 8_541_480}
    assert hi - lo - d.busy_ns(ev, lo, hi) == sum(split.values())


def test_recorded_decode_scopes(recorded):
    calls, scopes = s.decode_scopes(recorded.events)
    assert calls == 3
    assert scopes["attn_core"] == 104_702_018
    assert scopes["unscoped"] == 108_780_213
    assert scopes["mlp"] == 9_786_506
    assert set(scopes) == {"embed", "norm", "attn", "attn_core", "kv_write",
                           "mlp", "lm_head", "unscoped"}


def test_recorded_readers(recorded):
    got = {m: read(m, recorded) for m in METRICS}
    assert got == pytest.approx({
        "queued_p90_ms": 0.32295, "admit_share": 13.927158620789276,
        "idle_runtime_share": 0.17928574166233097,
        "idle_batcher_share": 3.0707396423752478,
        "idle_engine_share": 3.226153980925505,
        "decode_attn_core_ms": 34.900672666666665})
    idle = 100 * d.idle_share(recorded.events, recorded.lo_ns,
                              recorded.hi_ns)
    client = 100 * 82_950 / (recorded.hi_ns - recorded.lo_ns)
    assert sum(got[f"idle_{k}_share"] for k in s.LAYERS) + client \
        == pytest.approx(idle)


def test_accepted_reductions_read_the_first_five_elements(recorded):
    """The sixth element changes nothing the accepted readers compute."""
    ev, lo, hi = recorded.events, recorded.lo_ns, recorded.hi_ns
    five = [e[:5] for e in ev]
    assert d.busy_ns(ev, lo, hi) == d.busy_ns(five, lo, hi)
    assert d.top_ops(ev) == d.top_ops(five)
    assert d.op_ns(ev, "decode_attention") == d.op_ns(five, "decode_attention")
    assert [e[:5] for e in d.modules(ev, "jit__decode")] == \
        d.modules(five, "jit__decode")
