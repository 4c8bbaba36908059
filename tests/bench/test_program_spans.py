"""The program's spans as the per-layer readers see them: two waves of a
small graph traced on the CPU, interval arithmetic on hand-made events,
the HLO text's op names, and the six readers on a trace recorded on a
TPU v5e (two 16-clip waves of the DCASE program under the benchmark's
``bench.window`` / ``bench.step`` spans) with its program's HLO text."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, peaks, program_spans as ps
from bench import trace as tr
from bench.metrics import RunData, host_ms_per_span

TESTDATA = os.path.join(os.path.dirname(__file__), "..", "..", "bench",
                        "testdata")
PHASES = ("repro.wave.stack", "repro.wave.h2d", "repro.wave.launch",
          "repro.wave.fetch", "repro.wave.finish")


def _graph():
    from repro.signal import SignalGraph

    g = SignalGraph("spans")
    g.stft("spec", frame=64, hop=32)
    g.dnn("mask", "spec", fn=lambda p, z: jax.nn.sigmoid(jnp.abs(z) - 1.0))
    g.mul("enh", "spec", "mask")
    g.istft("out", "enh", hop=32)
    g.outputs("out")
    return g


def test_two_waves_nest_with_matching_wave_ids(tmp_path):
    from repro.serving import SignalRequest, SignalService

    svc = SignalService(batch_size=2, buckets=[256])
    svc.register("g", _graph())
    rng = np.random.default_rng(0)

    def wave(rid0):
        for rid in (rid0, rid0 + 1):
            svc.submit(SignalRequest(rid=rid, graph="g", samples=rng
                                     .standard_normal(200)
                                     .astype(np.float32)))
        assert len(svc.step()) == 2

    wave(0)                          # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        wave(2)
        wave(4)
    finally:
        jax.profiler.stop_trace()

    prog = ps.load(str(tmp_path))
    waves = prog.named("repro.wave")
    assert [w.args["wave"] for w in waves] == [1, 2]
    for w in waves:
        assert w.args["graph"] == "g" and w.args["rows"] == 2
        assert w.args["bucket"] == 256
        n = w.args["wave"]
        for phase in PHASES:
            mine = [s for s in prog.named(phase) if s.args["wave"] == n]
            assert len(mine) == 1, phase
            assert w.start_ns <= mine[0].start_ns \
                and mine[0].end_ns <= w.end_ns
        disp, = [d for d in prog.named("repro.sched.dispatch")
                 if d.args["wave"] == n]
        assert disp.start_ns <= w.start_ns and w.end_ns <= disp.end_ns
    for phase in PHASES:
        assert len(prog.named(phase)) == 2
    h2d = prog.named("repro.wave.h2d")[0]
    assert h2d.args["bytes"] == 2 * 256 * 4
    assert prog.named("repro.wave.fetch")[0].args["bytes"] > 0
    assert 0 < prog.named("repro.wave.stack")[0].args["pad_waste"] < 1
    assert not prog.named("repro.compile")      # compiled before the trace


def _ev(name, s, d):
    return tr.Event(name, float(s), float(d))


def test_innermost_pieces_and_idle_by_span():
    spans = [_ev("repro.sched.dispatch", 0, 100), _ev("repro.wave", 10, 80),
             _ev("repro.wave.stack", 10, 20), _ev("repro.wave.fetch", 50, 30),
             _ev("repro.sched.dispatch", 120, 10)]
    assert ps.innermost(spans) == [
        (0.0, 10.0, "repro.sched.dispatch"), (10.0, 30.0, "repro.wave.stack"),
        (30.0, 50.0, "repro.wave"), (50.0, 80.0, "repro.wave.fetch"),
        (80.0, 90.0, "repro.wave"), (90.0, 100.0, "repro.sched.dispatch"),
        (120.0, 130.0, "repro.sched.dispatch")]
    trace = tr.Trace({"/device:TPU:0": [_ev("%fusion.1", 40, 30)]}, [])
    idle = ps.idle_by_span(trace, spans, 0, 140)
    # gaps [0, 40) and [70, 140)
    assert idle["repro.sched.dispatch"] == pytest.approx(30e-9)
    assert idle["repro.wave.stack"] == pytest.approx(20e-9)
    assert idle["repro.wave"] == pytest.approx(20e-9)
    assert idle["repro.wave.fetch"] == pytest.approx(10e-9)
    assert idle["none"] == pytest.approx(30e-9)


HLO = """HloModule jit_call, is_scheduled=true

%fused_computation (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %gather.3 = f32[4]{0} gather(%param_0), metadata={op_name="jit(call)/spec/jit(k)/gather:spec.s1.butterfly/jit(_take)/gather"}
}

ENTRY %main (x.1: f32[4]) -> f32[4] {
  %x.1 = f32[4]{0} parameter(0), metadata={op_name="x"}
  %fusion = f32[4]{0} fusion(%x.1), kind=kLoop, calls=%fused_computation
  ROOT %shuffle_gemm.1 = f32[4]{0} custom-call(%fusion), custom_call_target="tpu_custom_call", metadata={op_name="jit(call)/spec/jit(k)/einsum:spec.s1.butterfly/shuffle_gemm"}
}
"""


def test_hlo_op_names_and_program_ops():
    hlo = ps.parse_hlo(HLO)
    assert hlo.module == "jit_call"
    # a fusion with no metadata of its own takes its root's
    assert ps.under_scope(hlo.op_names["%fusion"])
    assert not ps.under_scope(hlo.op_names["%shuffle_gemm.1"])
    assert ps.under_scope(hlo.op_names["%shuffle_gemm.1"], "einsum:")
    dev = "/device:TPU:0"
    trace = tr.Trace({dev: [
        _ev("%fusion = f32[4]{0} fusion(f32[4]{0} %x.1)", 10, 5),
        _ev("%shuffle_gemm.1 = f32[4]{0} custom-call(...)", 15, 5),
        _ev("%convert.1 = s32[4]{0} convert(...)", 30, 1)]}, [])
    prog = ps.Program([], {dev: [_ev("jit_call(123)", 9, 12),
                                 _ev("jit_convert_element_type(7)", 29, 3)]})
    ops = ps.program_ops(trace, prog, hlo, 0, 100)
    assert [(e.name.split()[0], op and ps.under_scope(op))
            for e, op in ops] == [("%fusion", True),
                                  ("%shuffle_gemm.1", False),
                                  ("%convert.1", None)]


# -- the six readers on the trace recorded on the chip --------------------------

def _recorded(monkeypatch, name):
    path = os.path.join(TESTDATA, name)
    monkeypatch.setattr(harness, "TRACE_DIR", path)
    trace = tr.load(path)
    steps = trace.spans_named("bench.step")
    waves = [{"bucket": 160000, "lens": [160000] * 16} for _ in steps]
    rec = type("Record", (), {"waves": waves})()
    return RunData(cfg={}, mod=None, record=rec, trace=trace, peaks={})


@pytest.fixture
def dcase(monkeypatch):
    run = _recorded(monkeypatch, "dcase_spans.xplane.pb")
    with open(os.path.join(TESTDATA, "dcase_spans.hlo.txt")) as f:
        text = f.read()
    monkeypatch.setattr(ps, "program_texts",
                        lambda run: {(160000, 16): text})
    return run, text


READERS = ("sched_ms_per_wave", "stack_ms_per_wave", "h2d_ms_per_wave",
           "fetch_host_ms_per_wave", "finish_ms_per_wave",
           "gather_device_ms_per_wave")


@pytest.mark.parametrize("name", READERS)
def test_reader_on_recorded_trace(dcase, name):
    run, _ = dcase
    v = harness.metric_reader(f"{name}.offline")(run)
    assert v is not None and 0 < v < 63.0      # ms of a 63 ms wave


def test_recorded_host_phases_add_up_to_host_time(dcase):
    """The host phases and the launch spans split the host part of the
    ``bench.step`` span around each wave: within 1 ms or 10% of it."""
    run, _ = dcase
    parts = sum(harness.metric_reader(f"{n}.offline")(run)
                for n in READERS[:-1])
    parts += ps.ms_per_wave(run, "repro.wave.launch")
    whole = host_ms_per_span(run, {"bench.step"}, "bench.step")
    assert abs(parts - whole) <= max(1.0, 0.1 * whole)


def test_recorded_ops_map_to_hlo_and_gathers_count_exactly(dcase):
    run, text = dcase
    hlo = ps.parse_hlo(text)
    t0, t1 = tr.window(run.trace)
    ops = ps.program_ops(run.trace, ps.load(harness.TRACE_DIR), hlo, t0, t1)
    total = sum(e.dur_ns for e, _ in ops)
    assert sum(e.dur_ns for e, op in ops if op is not None) >= 0.99 * total
    gather = sum(e.dur_ns for e, op in ops if op and ps.under_scope(op))
    waves = len(run.trace.spans_named("bench.step"))
    assert ps.scoped_device_ms_per_wave(run) == \
        pytest.approx(gather / waves / 1e6)
    assert 0 < gather < total


def test_roofline_takes_kernel_calls_from_the_program_text(dcase):
    """The recorded waves' 11 ``shuffle_gemm`` calls each, costed from
    the shapes in the HLO text, over their device time: the share the
    chip run that recorded the trace read."""
    run, _ = dcase
    run.peaks = peaks.for_device("TPU v5 lite")
    v = harness.metric_reader("shuffle_gemm_roofline.offline")(run)
    assert v == pytest.approx(23.088, abs=1e-3)


def test_readers_find_nothing_in_a_trace_without_program_spans(monkeypatch):
    """The parent program writes no ``repro.*`` spans: every reader
    returns None, and the harness leaves the metric out."""
    run = _recorded(monkeypatch, "dcase_two_waves.xplane.pb")
    for name in READERS:
        assert harness.metric_reader(f"{name}.offline")(run) is None
