"""The trace reduction, pinned on a trace recorded on a TPU v5e."""
import json

import pytest

import devtrace as d
from conftest import CHIP


@pytest.fixture(scope="module")
def trace():
    with open(CHIP / "tests" / "data" / "trace_v5e_decode.json") as f:
        ev = json.load(f)["events"]
    lo, hi = d.window(ev)
    return d.clip(ev, lo, hi), lo, hi


def test_window_and_busy_time(trace):
    ev, lo, hi = trace
    assert hi - lo == 42_000_000
    assert d.busy_ns(ev, lo, hi) == 37_028_868
    assert d.idle_share(ev, lo, hi) == pytest.approx(1 - 37_028_868 / 42e6)


def test_kernel_time_by_name(trace):
    ev, _, _ = trace
    assert d.op_ns(ev, "decode_attention") == 3_249_890
    assert d.op_ns(ev, "flash_attention") == 193_783
    assert d.op_ns(ev, "no_such_kernel") == 0


def test_breakdown(trace):
    ev, lo, hi = trace
    top = d.top_ops(ev, 3)
    assert [n for n, _ in top] == ["copy", "fusion", "decode_attention"]
    assert top[0][1] == pytest.approx(0.0238169)
    gaps = d.idle_gaps(ev, lo, hi, 2)
    assert gaps[0] == ["client", pytest.approx(0.003427862)]


def test_union_counts_overlap_once():
    ev = [["/device:TPU:0", d.OPS_LINE, "%a.1", 0, 10],
          ["/device:TPU:0", d.OPS_LINE, "%b.2", 5, 10],
          ["/device:TPU:0", d.OPS_LINE, "%c.3", 30, 10],
          ["/host:CPU", "python3", "bench.tick", 14, 20]]
    assert d.busy_ns(ev, 0, 50) == 25
    assert d.idle_gaps(ev, 0, 50) == [["bench.tick", 15e-9], ["client", 10e-9]]


@pytest.mark.parametrize("op, base", [
    ("%copy.99.remat2", "copy"), ("%decode_attention.3", "decode_attention"),
    ("%constant_dynamic-update-slice_fusion.4",
     "constant_dynamic-update-slice_fusion")])
def test_base_name(op, base):
    assert d.base_name(op) == base
