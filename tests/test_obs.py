"""SigTrace observability: spans, tracer, metrics registry, report.

Covers the acceptance invariants:

  * ``obs.span`` writes ``repro.<name>`` into a ``jax.profiler`` trace
    while a profiler session records, an ``X`` event of the Chrome JSON
    while ``obs.ENABLED``, both when both are on, and is the shared
    no-op (no allocation) while neither is;
  * exported Chrome Trace JSON parses, holds only the phases the tracer
    writes, timestamps are monotonic per ``tid`` in record order for
    non-``X`` phases, counters non-negative;
  * histogram p50/p95/p99 on a known distribution;
  * an end-to-end traced serving run contains the wave-phase /
    stream / DecodeWave spans and the occupancy + plan-cache counter
    tracks, and the rendered report's percentiles match the histograms
    they came from;
  * every stage and ``gather:``/``einsum:`` step scope reaches the
    compiled program's op metadata;
  * ``value_and_grad`` on a non-differentiable backend is a hard error
    (no silent or warned rebind, no counter).
"""

import json
import tracemalloc
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.obs.metrics import Histogram, percentile
from repro.obs.trace import TraceError, Tracer, validate_trace


@pytest.fixture(autouse=True)
def _clean_obs():
    """Every test starts and ends with instrumentation off and empty."""
    obs.reset()
    yield
    obs.reset()


def _graph(frame=64, hop=32):
    from repro.signal import SignalGraph

    g = SignalGraph("obs_fig9")
    g.stft("spec", frame=frame, hop=hop)
    g.dnn("mask", "spec", fn=lambda p, z: jax.nn.sigmoid(jnp.abs(z) - 1.0))
    g.mul("enh", "spec", "mask")
    g.istft("out", "enh", hop=hop)
    g.outputs("out")
    return g


# --------------------------------------------------------------------------
# Tracer
# --------------------------------------------------------------------------

def test_trace_export_parses_and_validates(tmp_path):
    obs.enable()
    with obs.span("SignalService", "wave", wave=0):
        with obs.span("SignalService", "wave.launch", wave=0) as sp:
            sp.set(entry="masked")
    with obs.span("DecodeWave", "engine.prefill", size=2):
        pass
    obs.instant("SignalService", "admit", rid=7)
    obs.tracer().counter("occupancy", {"dsp_cycles": 10, "llm_cycles": 20})
    path = tmp_path / "trace.json"
    obs.get_tracer().export(str(path))

    doc = json.loads(path.read_text())
    assert isinstance(doc["traceEvents"], list)
    stats = validate_trace(str(path))
    assert stats["phases"]["X"] == 3
    assert stats["phases"]["i"] == 1 and stats["phases"]["C"] == 1
    assert "B" not in stats["phases"] and "E" not in stats["phases"]
    launch = [ev for ev in doc["traceEvents"] if ev["name"] == "wave.launch"]
    assert launch[0]["args"] == {"wave": 0, "entry": "masked"}
    # lanes are named via metadata events
    names = {ev["args"]["name"] for ev in doc["traceEvents"]
             if ev["ph"] == "M" and ev["name"] == "thread_name"}
    assert {"SignalService", "DecodeWave", "counters"} <= names


def test_validate_rejects_unbalanced_and_negative():
    # B/E events are phases the tracer never writes (spans are X events)
    with pytest.raises(TraceError):
        validate_trace({"traceEvents": [
            {"ph": "B", "pid": 1, "tid": 1, "ts": 0.0, "name": "tick"}]})
    with pytest.raises(TraceError):
        validate_trace({"traceEvents": [
            {"ph": "E", "pid": 1, "tid": 1, "ts": 0.0, "name": "tick"}]})
    with pytest.raises(TraceError):
        validate_trace({"traceEvents": [
            {"ph": "i", "pid": 1, "tid": 1, "ts": -5.0, "name": "x"}]})
    with pytest.raises(TraceError):
        validate_trace({"traceEvents": [
            {"ph": "C", "pid": 1, "tid": 1, "ts": 0.0, "name": "occ",
             "args": {"v": -1.0}}]})
    with pytest.raises(TraceError):
        validate_trace({"traceEvents": [
            {"ph": "i", "pid": 1, "tid": 3, "ts": 9.0, "name": "a"},
            {"ph": "i", "pid": 1, "tid": 3, "ts": 4.0, "name": "b"}]})
    with pytest.raises(TraceError):
        validate_trace({"traceEvents": [
            {"ph": "X", "pid": 1, "tid": 1, "ts": 0.0, "name": "w",
             "dur": -1.0}]})


def test_tracer_timestamps_monotonic_per_tid():
    tr = Tracer()
    for i in range(50):
        tr.instant("lane_a", f"e{i}")
        tr.counter("c", {"v": float(i)})
    assert validate_trace(tr.to_dict())["events"] == 100


# --------------------------------------------------------------------------
# Metrics
# --------------------------------------------------------------------------

def test_histogram_percentiles_known_distribution():
    h = Histogram()
    for v in range(1, 101):          # 1..100, nearest-rank percentiles
        h.record(float(v))
    assert h.percentile(0.50) == 50.0
    assert h.percentile(0.95) == 95.0
    assert h.percentile(0.99) == 99.0
    s = h.summary()
    assert s["count"] == 100 and s["min"] == 1.0 and s["max"] == 100.0
    assert s["mean"] == pytest.approx(50.5)
    assert percentile([1.0, 2.0, 3.0], 0.50) == 2.0


def test_histogram_downsample_keeps_exact_count_and_extremes():
    h = Histogram(max_samples=64)
    for v in range(1, 1001):
        h.record(float(v))
    s = h.summary()
    assert s["count"] == 1000 and s["min"] == 1.0 and s["max"] == 1000.0
    assert 300.0 <= s["p50"] <= 700.0     # approximate after downsample


def test_registry_counters_gauges():
    reg = obs.get_registry()
    reg.counter("a").inc()
    reg.counter("a").inc(4)
    reg.gauge("g").set(2.5)
    snap = reg.snapshot()
    assert snap["counters"]["a"] == 5
    assert snap["gauges"]["g"] == 2.5
    reg.reset()
    assert reg.snapshot()["counters"] == {}


# --------------------------------------------------------------------------
# Zero-cost-when-off
# --------------------------------------------------------------------------

def test_disabled_mode_records_nothing():
    from repro.serving import SignalService, SignalRequest

    assert not obs.ENABLED
    svc = SignalService(batch_size=2)
    svc.register("fig9", _graph())
    rng = np.random.default_rng(0)
    for rid in range(3):
        svc.submit(SignalRequest(
            rid=rid, graph="fig9",
            samples=rng.standard_normal(200).astype(np.float32)))
    while svc.pending():
        svc.step()
    assert obs.get_tracer().events() == []
    assert obs.get_registry().snapshot()["counters"] == {}


def test_disabled_hook_allocates_nothing():
    # the hook shapes used at the instrumentation sites: a span whose
    # exit args are computed only for a live span, and a guarded clock
    def hook():
        with obs.span("SignalService", "wave.stack", wave=3) as sp:
            if sp:
                sp.set(pad_waste=0.5)
        return obs.now() if obs.ENABLED else 0

    assert obs.span("SignalService", "wave") is obs.NO_SPAN
    assert not obs.NO_SPAN
    hook()                           # warm up
    tracemalloc.start()
    for _ in range(1000):
        hook()
    _, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    assert peak < 4096               # no per-call allocation
    assert obs.get_tracer().events() == []


def _profiled(tmp_path, fn):
    """Run ``fn`` inside a jax.profiler session; the ``repro.*`` host
    events of the trace it wrote as ``(name, args)``."""
    import glob

    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    return [(e.name, dict(e.stats))
            for plane in ProfileData.from_file(path).planes
            for line in plane.lines for e in line.events
            if e.name.startswith("repro.")]


def test_span_lands_in_profiler_trace_without_obs(tmp_path):
    def work():
        assert obs.span("SigSched", "sched.dispatch") is not obs.NO_SPAN
        with obs.span("SignalService", "wave.h2d", wave=4, graph="g") as sp:
            sp.set(bytes=1024)

    events = _profiled(tmp_path, work)
    assert ("repro.wave.h2d", {"wave": 4, "graph": "g", "bytes": 1024}) \
        in events
    assert obs.get_tracer().events() == []      # the JSON sink stayed off
    assert obs.span("SignalService", "wave") is obs.NO_SPAN


def test_span_feeds_both_sinks(tmp_path):
    obs.enable()

    def work():
        with obs.span("SignalService", "wave.fetch", wave=1) as sp:
            sp.set(bytes=8)

    events = _profiled(tmp_path, work)
    assert ("repro.wave.fetch", {"wave": 1, "bytes": 8}) in events
    xs = [ev for ev in obs.get_tracer().events() if ev["ph"] == "X"]
    assert [(ev["name"], ev["args"]) for ev in xs] == \
        [("wave.fetch", {"wave": 1, "bytes": 8})]


# --------------------------------------------------------------------------
# End-to-end: traced serving run
# --------------------------------------------------------------------------

def test_traced_serving_run_has_expected_lanes(tmp_path):
    from repro.serving import SignalService, SignalRequest

    obs.enable()
    svc = SignalService(batch_size=2, block_frames=2)
    svc.register("fig9", _graph())
    rng = np.random.default_rng(1)
    for rid in range(4):
        svc.submit(SignalRequest(
            rid=rid, graph="fig9",
            samples=rng.standard_normal(
                int(rng.integers(100, 400))).astype(np.float32)))
    while svc.pending():
        svc.step()
    s = svc.open_stream("fig9")
    s.feed(jnp.asarray(rng.standard_normal(256).astype(np.float32)))
    svc.stream_step()
    s.close()

    path = str(tmp_path / "svc_trace.json")
    obs.get_tracer().export(path)
    stats = validate_trace(path)
    doc = json.loads(open(path).read())
    names = {(ev["tid"], ev["name"]) for ev in doc["traceEvents"]
             if ev["ph"] == "X"}
    lanes = {ev["args"]["name"]: ev["tid"] for ev in doc["traceEvents"]
             if ev["ph"] == "M" and ev["name"] == "thread_name"}
    for phase in ("wave", "wave.stack", "wave.h2d", "wave.launch",
                  "wave.fetch", "wave.finish", "compile"):
        assert (lanes["SignalService"], phase) in names
    assert (lanes["SigSched"], "sched.dispatch") in names
    assert (lanes["Streaming"], "stream.tick") in names
    assert (lanes["Streaming"], "stream.core") in names
    assert stats["phases"]["X"] >= 4
    waves = [ev for ev in doc["traceEvents"] if ev["name"] == "wave"]
    assert sorted(ev["args"]["wave"] for ev in waves) == \
        list(range(len(waves)))

    # metrics side: latency histogram, byte and plan-cache counters fed
    snap = obs.get_registry().snapshot()
    assert snap["histograms"]["service.latency_us.fig9"]["count"] == 4
    assert any(k.startswith("plan_cache.") for k in snap["counters"])
    assert snap["counters"]["service.h2d_bytes"] >= 4 * 100 * 4
    assert snap["counters"]["service.d2h_bytes"] > 0


def test_traced_coscheduler_tick_counters():
    from repro.configs import get_config
    from repro.models.zoo import get_model
    from repro.serving import (CoScheduler, Request, SignalRequest,
                               SignalService, ServingEngine)

    obs.enable()
    cfg = get_config("starcoder2-3b").reduced(
        n_layers=1, d_model=16, n_heads=2, d_ff=32, vocab=64)
    bundle = get_model(cfg)
    eng = ServingEngine(bundle, batch_size=2)
    eng.load(bundle.init(jax.random.PRNGKey(0)))
    svc = SignalService(batch_size=2)
    svc.register("fig9", _graph())
    sched = CoScheduler(eng, svc)
    rng = np.random.default_rng(2)
    sched.submit_signal(SignalRequest(
        rid=0, graph="fig9",
        samples=rng.standard_normal(200).astype(np.float32)))
    sched.submit_llm(Request(rid=1, prompt=[1, 2, 3], max_new=2))
    while not sched.idle:
        sched.tick()

    doc = obs.get_tracer().to_dict()
    counter_names = {ev["name"] for ev in doc["traceEvents"]
                     if ev["ph"] == "C"}
    assert "occupancy" in counter_names
    assert any(n.startswith("plan_cache/") for n in counter_names)
    x_names = {ev["name"] for ev in doc["traceEvents"] if ev["ph"] == "X"}
    assert "cosched.tick" in x_names and "engine.prefill" in x_names
    assert "engine.decode_step" in x_names
    validate_trace(doc)
    snap = obs.get_registry().snapshot()
    assert snap["counters"]["engine.prefills"] >= 1
    assert snap["counters"]["sched.ticks"] == sched.ticks


# --------------------------------------------------------------------------
# Named scopes in the compiled program
# --------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_step_scopes_reach_compiled_hlo(backend):
    import re

    from repro.core.exec_ir import step_kind

    c = _graph().compile(256, backend=backend)
    x = jax.ShapeDtypeStruct((2, 256), jnp.float32)
    vf = jax.ShapeDtypeStruct((2,), jnp.int32)
    text = c.masked_jit().lower(x, vf, c.init_params()).compile().as_text()
    scopes = {part for op in re.findall(r'op_name="([^"]*)"', text)
              for part in op.split("/")}
    steps = [s for st in c.program.stages for s in st.steps]
    assert {st.name for st in c.program.stages} <= scopes
    kinds = {step_kind(s) for s in steps}
    assert {"gather", "einsum"} <= kinds
    for s in steps:
        if step_kind(s) != "lambda":
            assert f"{step_kind(s)}:{s.name}" in scopes, s.name


def test_pallas_group_scopes_split_gather_from_kernel():
    """A gather paired with a shuffle_gemm call: the XLA gather runs
    under a ``gather:`` step scope and the kernel call (interpreted on
    the CPU, named ``shuffle_gemm``) under the einsum step's."""
    import re

    c = _graph().compile(256, backend="pallas")
    x = jax.ShapeDtypeStruct((2, 256), jnp.float32)
    vf = jax.ShapeDtypeStruct((2,), jnp.int32)
    text = c.masked_jit().lower(x, vf, c.init_params()).compile().as_text()

    def step_scope(op_name):         # the innermost <kind>:<step> scope
        return [p for p in op_name.split("/")
                if p.startswith(("gather:", "einsum:", "lambda:"))][-1]

    ops = set(re.findall(r'op_name="([^"]*)"', text))
    gathers = [o for o in ops if o.endswith("_take)/gather")]
    kernel = [o for o in ops if "/shuffle_gemm/" in o]
    assert gathers and kernel
    assert all(step_scope(o).startswith("gather:") for o in gathers)
    assert all(step_scope(o).startswith("einsum:") for o in kernel)
    # the paired gather step's own name reaches the gather it lowers to
    first = c.program.stages[0].steps[0]
    assert any(f"/gather:{first.name}/" in o for o in gathers)


# --------------------------------------------------------------------------
# Report
# --------------------------------------------------------------------------

def test_report_percentiles_match_histograms():
    reg = obs.get_registry()
    h = reg.histogram("service.latency_us.fig9")
    for v in range(1, 101):
        h.record(float(v))
    ho = reg.histogram("service.latency_us.fig9/out")
    for v in range(1, 11):
        ho.record(float(v))
    rep = obs.build_report()
    entry = rep["latency_us"]["fig9"]
    assert entry["p50"] == h.percentile(0.50)
    assert entry["p95"] == h.percentile(0.95)
    assert entry["outputs"]["out"]["p50"] == ho.percentile(0.50)
    assert rep["schema_version"] == obs.REPORT_SCHEMA_VERSION
    text = obs.render_report(rep)
    assert "fig9" in text and "p95" in text


def test_report_backend_routes_and_counters():
    reg = obs.get_registry()
    reg.counter("backend.reference.fabric_emulated").inc(3)
    reg.counter("backend.pallas.fabric_fused").inc(2)
    rep = obs.build_report()
    assert rep["backend_routes"]["reference"]["fabric_emulated"] == 3
    assert rep["backend_routes"]["pallas"]["fabric_fused"] == 2
    assert "reference" in obs.render_report(rep)


# --------------------------------------------------------------------------
# value_and_grad on a non-differentiable backend: hard error, no counter
# --------------------------------------------------------------------------

def test_value_and_grad_non_differentiable_hard_errors():
    """Since the pallas kernels gained custom VJPs, no shipped backend
    re-binds under ``value_and_grad`` — and a future backend declaring
    ``differentiable = False`` must be a hard error, never a silent (or
    warned) backend change.  The old ``graph.backend_rebind`` counter is
    gone with the rebind path."""
    from repro.signal import SignalGraph
    from repro.signal.backends import ReferenceBackend

    class FrozenBackend(ReferenceBackend):
        name = "frozen"
        differentiable = False

    g = SignalGraph("nodiff")
    g.fir("front", "input", taps=np.array([1.0, 0.0], np.float32))
    g.outputs("front")
    c = g.compile(64, backend=FrozenBackend())

    def loss(outs, target):
        return jnp.mean((outs["front"] - target) ** 2)

    with warnings.catch_warnings():
        warnings.simplefilter("error")       # no warning path anymore
        with pytest.raises(ValueError, match="frozen.*differentiable"):
            c.value_and_grad(loss, wrt=("front",))
    counters = obs.get_registry().snapshot()["counters"]
    assert "graph.backend_rebind" not in counters

    # pallas itself differentiates — building and running the gradient
    # fn on the pallas binding is warning-free and rebind-free.
    cp = g.compile(64, backend="pallas")
    assert cp.backend.differentiable
    x = jnp.zeros((1, 64), jnp.float32)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        vag = cp.value_and_grad(loss, wrt=("front",))
        vag(cp.init_params(), x, jnp.zeros_like(x))
