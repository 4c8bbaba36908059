"""The trace reduction: interval arithmetic on hand-made events, and the
whole reduction on a small trace recorded on a TPU v5e (two 16-clip
waves of the DCASE program, each inside a ``bench.step`` span, with a
20 ms ``bench.wait`` after each, all inside ``bench.window``)."""

import os

import numpy as np
import pytest

from bench import trace as tr
from bench.metrics import RunData, host_ms_per_span

TESTDATA = os.path.join(os.path.dirname(__file__), "..", "..", "bench",
                        "testdata", "dcase_two_waves.xplane.pb")


def test_merge_covered_and_gaps():
    m = tr.merge([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert m.tolist() == [[0, 3], [5, 8]]
    assert tr.covered(m, 2, 6) == 2.0
    assert tr.gaps(m, -1, 10) == [(-1, 0), (3, 5), (8, 10)]
    assert tr.gaps(tr.merge([]), 0, 4) == [(0, 4)]


def _ev(name, s, d):
    return tr.Event(name, float(s), float(d))


def test_gap_labels_and_summary_on_hand_made_events():
    trace = tr.Trace(
        {"/device:TPU:0": [_ev("fusion.1", 10, 20), _ev("shuffle_gemm.3",
                                                        30, 10),
                           _ev("fusion.1", 70, 10)]},
        [_ev("bench.window", 0, 100), _ev("bench.step", 5, 40),
         _ev("bench.wait", 45, 20), _ev("bench.step", 65, 30)])
    s = tr.summary(trace)
    assert s["busy_s"] == pytest.approx(40e-9)
    assert s["window_s"] == pytest.approx(100e-9)
    assert s["breakdown"]["device_ops"][0] == ["fusion.1",
                                               pytest.approx(30e-9)]
    op = tr.Event("%shuffle_gemm.11 = f32[16,1,4,159744]{3,2,1,0} "
                  "custom-call(f32[16,1,4,159744]{3,2,1,0} %fusion.12), "
                  "custom_call_target=\"tpu_custom_call\"", 0, 1)
    use = tr.Event("%slice.43 = f32[16,1,4,159232]{3,2,1,0} slice("
                   "f32[16,1,4,159744]{3,2,1,0} %shuffle_gemm.11)", 0, 1)
    assert op.op == "%shuffle_gemm.11 custom-call"
    assert op.is_instruction("shuffle_gemm")
    assert not use.is_instruction("shuffle_gemm") and use.op == "%slice.43 slice"
    idle = dict(s["breakdown"]["idle_gaps"])
    # gaps: [0,10) step, [40,70) mostly wait, [80,100) step
    assert idle["bench.wait"] == pytest.approx(30e-9)
    assert idle["bench.step"] == pytest.approx(30e-9)
    run = RunData(cfg={}, mod=None, record=None, trace=trace, peaks={})
    # step spans: 40 + 30 ns long, 30 + 10 ns of device time inside
    assert host_ms_per_span(run, {"bench.step"}, "bench.step") \
        == pytest.approx((70 - 40) / 2 / 1e6)


@pytest.fixture(scope="module")
def recorded():
    return tr.load(TESTDATA)


def test_recorded_trace_has_device_ops_and_spans(recorded):
    assert recorded.n_devices == 1
    assert len(recorded.spans_named("bench.window")) == 1
    steps = recorded.spans_named("bench.step")
    assert len(steps) == 2
    t0, t1 = tr.window(recorded)
    assert all(t0 <= s.start_ns and s.end_ns <= t1 for s in steps)
    kernels = [e for e in recorded.ops() if e.is_instruction("shuffle_gemm")]
    assert len(kernels) == 2 * 11        # 11 calls in a 16-clip wave
    # the device work lies inside the steps, on the profiler's clock
    dev = next(iter(recorded.device_ops))
    merged = recorded.busy(dev)
    inside = sum(tr.covered(merged, s.start_ns, s.end_ns) for s in steps)
    assert inside == pytest.approx(tr.covered(merged, t0, t1), rel=0.05)


def test_recorded_trace_summary(recorded):
    s = tr.summary(recorded)
    assert 0 < s["busy_s"] < s["window_s"]
    idle = dict(s["breakdown"]["idle_gaps"])
    # each 20 ms wait is idle on the device
    assert idle["bench.wait"] == pytest.approx(0.04, rel=0.25)
    ops = s["breakdown"]["device_ops"]
    assert len(ops) <= 10 and all(v > 0 for _, v in ops)
    assert np.all(np.diff([v for _, v in ops]) <= 0)
