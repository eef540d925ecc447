"""The trace reduction on a small trace recorded on a TPU v5e: the CNet
cell served 0.25 s of Poisson traffic under the profiler
(``data/cnet_small.xplane.pb``); ``data/cnet_small.json`` holds what the
run recorded beside it (the dispatches, as (rung, real requests))."""
import json
import os

import pytest

from bench import trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TRACE = os.path.join(DATA, "cnet_small.xplane.pb")


@pytest.fixture(scope="module")
def summary():
    return trace_reduce.Summary(*trace_reduce.load(TRACE))


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "cnet_small.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name,base", [
    ("%conv2d_int8.3 = s8[32,256,256,48] custom-call(s8[32,258,258,2])",
     "conv2d_int8"),
    ("%int8_matmul.2 = s8[32,96] custom-call(s8[32,32896])", "int8_matmul"),
    ("%pad.18.clone = s8[1] pad(s8[1])", "pad"),
    ("%copy-start.10 = (f32[48]) copy-start(f32[48])", "copy-start"),
    ("clamp_convert_fusion", "clamp_convert_fusion"),
])
def test_op_base(name, base):
    assert trace_reduce.op_base(name) == base


def test_one_chip_and_one_window(summary):
    assert len(summary.devices) == 1
    lo, hi = summary.window
    assert 0.24 < (hi - lo) * 1e-9 < 0.3


def test_plan_calls_match_the_dispatches(summary, recorded):
    # every dispatch of CNet's accel plan runs conv2d_int8 once per conv
    # (3) and int8_matmul once per dense layer (fc1, head); the trace
    # holds the first dispatches of the window whole, in order
    calls = summary.plan_calls()
    assert 0 < len(calls) <= len(recorded["dispatches"])
    for call in calls:
        assert call["conv2d_int8"][1] == 3
        assert call["int8_matmul"][1] == 2
        assert call["conv2d_int8"][0] > call["int8_matmul"][0] > 0
    assert [[k, list(v)] for c in calls for k, v in sorted(c.items())] == \
        [[k, list(v)] for c in recorded["plan_calls"]
         for k, v in sorted(c.items())]


def test_busy_time_is_a_union_inside_the_window(summary, recorded):
    lo, hi = summary.window
    busy = summary.busy_seconds(lo, hi)
    ops = [op for op in summary.devices[0]
           if op.start >= lo and op.end <= hi]
    assert 0 < busy <= (hi - lo) * 1e-9
    assert busy <= sum(op.end - op.start for op in ops) * 1e-9 + 1e-12
    assert busy >= max(op.end - op.start for op in ops) * 1e-9
    assert busy == pytest.approx(recorded["busy_s"], rel=1e-9)


def test_breakdown(summary, recorded):
    b = summary.breakdown()
    assert b == recorded["breakdown"]
    assert len(b["device_ops"]) == trace_reduce.TOP
    assert "conv2d_int8" in [name for name, _ in b["device_ops"]]
    seconds = [s for _, s in b["idle_gaps"]]
    assert seconds == sorted(seconds, reverse=True)
    lo, hi = summary.window
    idle = (hi - lo) * 1e-9 - summary.busy_seconds(lo, hi)
    assert 0 < sum(seconds) <= idle + 1e-9
    labels = {label for label, _ in b["idle_gaps"]}
    assert labels <= {"bench.submit", "bench.step", "bench.dispatch",
                      "bench.retire", trace_reduce.NO_SPAN}
