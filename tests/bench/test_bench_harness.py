"""The harness finds every piece by its name in BENCHMARK.json, the file
keeps to its contract, and a run with the timed path broken underneath
comes out not correct (the chip gate skipped, tiny sizes on the CPU)."""

import json
import os
import re

import numpy as np
import pytest

from bench import harness, peaks

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def spec():
    return harness.load_benchmark()


def test_every_piece_is_found_by_name(spec):
    for w in spec["workloads"]:
        cfg, mod = harness.load_config(w["config"])
        assert cfg["name"] == w["config"]
        for fn in ("build_graph", "make_params", "make_audio", "reference",
                   "flops"):
            assert callable(getattr(mod, fn))
        traffic = harness.load_traffic(w["traffic"])
        assert traffic["mode"] in ("open_loop", "backlog", "streams")
    for m in spec["per_layer"]:
        assert callable(harness.metric_reader(m["name"]))
    with pytest.raises(KeyError):
        harness.metric_reader("no_such_metric.anywhere")
    with pytest.raises(KeyError):
        harness.workload(spec, "no-such-cell")


def test_benchmark_json_keeps_its_contract(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    root = harness.ROOT
    for p in spec["paths"]:
        assert os.path.isdir(os.path.join(root, p))
    cells = {w["name"] for w in spec["workloads"]}
    configs = {c["name"] for c in spec["configs"]}
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in e2e
    for c in spec["configs"]:
        assert NAME.match(c["name"]) and os.path.exists(
            os.path.join(root, c["file"]))
        with open(os.path.join(root, c["file"])) as f:
            assert sorted(json.load(f)["reduced"]) == sorted(c["reduced"])
        assert any(w["config"] == c["name"] for w in spec["workloads"])
    for w in spec["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in configs
        assert w["chips"] in (1, 4) and 0 < len(w["why"]) <= 200
    for kind in ("end_to_end", "per_layer"):
        for m in spec[kind]:
            assert NAME.match(m["name"]) and UNIT.match(m["unit"])
            assert m["better"] in ("lower", "higher")
            assert set(m.get("workloads", cells)) <= cells
    for m in spec["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
    for cell in cells:
        got = {m["name"] for m in harness.metrics_for(spec, cell,
                                                      "end_to_end")}
        assert "setup_s" in got and len(got) >= 2
        assert harness.metrics_for(spec, cell, "per_layer")
        for m in harness.metrics_for(spec, cell, "per_layer"):
            assert m["moves"] in got


def test_peaks_refuse_an_unknown_device():
    assert peaks.for_device("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.for_device("cpu")


# -- runs on the CPU at tiny sizes, with the timed path broken -----------------

TINY = {
    "dcase2020-t2-ae": (
        {"service": {"batch_size": 2, "backend": "reference",
                     "buckets": [8192]}},
        {"rate_per_s": 10, "pool_seconds": 2, "check_sample": 4,
         "length": {"dist": "fixed", "value": 8192, "min": 8192,
                    "max": 8192}}),
    "fig9-speech-enhance": (
        {"service": {"batch_size": 2, "backend": "reference",
                     "buckets": [4096], "block_frames": 8}},
        {"rate_per_s": 10, "pool_seconds": 2, "check_sample": 8,
         "length": {"dist": "lognormal", "median": 3000, "sigma": 0.3,
                    "min": 2000, "max": 4096},
         "sessions": 2, "check_sessions": 2}),
}


# every traffic mode, including the Fig-9 mixes that no cell runs yet
CELLS = {"dcase-offline": ("dcase2020-t2-ae", "dcase-backlog"),
         "dcase-oneshot": ("dcase2020-t2-ae", "dcase-poisson"),
         "fig9-stream": ("fig9-speech-enhance", "fig9-stream-sessions"),
         "fig9-oneshot": ("fig9-speech-enhance", "fig9-oneshot-poisson")}


def _run(cell_name, seed=2 ** 31 + 5):
    spec = harness.load_benchmark()
    config, traffic = CELLS[cell_name]
    wl = {"name": cell_name, "config": config, "traffic": traffic,
          "chips": 1}
    cfg_over, tr_over = TINY[config]
    result, notes = harness.run(
        wl, spec, seed, 1.0, False, 0.0, require_tpu=False,
        cfg_override=cfg_over, traffic_override=tr_over,
        compile_cache=False)
    return result, result["checks"]


def test_sound_offline_and_stream_runs_are_correct():
    for cell in ("dcase-offline", "fig9-stream"):
        result, checks = _run(cell)
        assert result["correct"], (cell, checks)
        assert result["attempted"] > 0 and result["failed"] == 0
        assert set(result["metrics"]) >= {"setup_s"}
        assert list(result)[-1] == "checks"


def test_sound_oneshot_run_is_correct_and_times_each_request():
    result, checks = _run("dcase-oneshot")
    assert result["correct"], checks
    assert result["attempted"] == 10 and result["failed"] == 0
    m = result["metrics"]
    assert set(m) == {"setup_s", "latency_p50_ms", "latency_p95_ms"}
    assert 0 < m["latency_p50_ms"]["value"] <= m["latency_p95_ms"]["value"]


def test_backlog_waves_record_the_bucket_they_ran_in():
    """Clips shorter than their bucket: each wave is recorded at the
    pinned bucket, the program it ran, so the padding shows."""
    import time
    from bench import drive
    wl = {"name": "padded", "config": "dcase2020-t2-ae",
          "traffic": "dcase-backlog", "chips": 1}
    cfg_over, tr_over = TINY["dcase2020-t2-ae"]
    tr_over = dict(tr_over, length={"dist": "fixed", "value": 6000})
    cfg, mod, traffic, _, svc = harness.build(wl, 3, cfg_over, tr_over)
    cell = drive.Cell(svc, cfg["name"], cfg, mod, traffic, 3, 0.5,
                      time.perf_counter(), False, lambda: None,
                      lambda: None, drive.CompileCounter())
    rec = drive.backlog(cell)
    assert rec.waves and all(w["bucket"] == 8192 and w["lens"] == [6000] * 2
                             for w in rec.waves)
    pad = harness.metric_reader("pad_waste")(
        type("Run", (), {"record": rec})())
    assert pad == pytest.approx(100.0 * (8192 - 6000) / 8192)


def _alter(monkeypatch):
    """Every one-shot answer altered where it is produced."""
    from repro.serving.signal_service import SignalService
    real = SignalService._request_result

    def altered(self, *a, **k):
        res = dict(real(self, *a, **k))
        key = next(iter(res))
        arr = np.array(res[key], copy=True)
        arr.flat[0] += 0.5 * np.max(np.abs(arr)) + 1.0
        res[key] = arr
        return res
    monkeypatch.setattr(SignalService, "_request_result", altered)


def _drop_half(monkeypatch):
    """Half of each wave's answers never delivered."""
    from repro.serving.signal_service import SignalService
    real = SignalService.step

    def step(self, *a, **k):
        res = real(self, *a, **k)
        keep = sorted(res)[: max(1, len(res) // 2)] if len(res) > 1 \
            else [rid for rid in res if rid % 2 == 0]
        return {rid: res[rid] for rid in keep}
    monkeypatch.setattr(SignalService, "step", step)


def _alter_stream(monkeypatch):
    """Every streamed piece altered where it is read."""
    from repro.serving.signal_service import StreamSession
    real = StreamSession.read

    def read(self):
        out = real(self)
        if "out" in out and np.size(out["out"]):
            out["out"] = np.asarray(out["out"]) + 1.0
        return out
    monkeypatch.setattr(StreamSession, "read", read)


@pytest.mark.parametrize("cell,fault", [
    ("dcase-offline", _alter), ("dcase-offline", _drop_half),
    ("dcase-oneshot", _alter), ("dcase-oneshot", _drop_half),
    ("fig9-oneshot", _alter), ("fig9-oneshot", _drop_half),
    ("fig9-stream", _alter_stream)])
def test_broken_timed_path_is_not_correct(monkeypatch, cell, fault):
    fault(monkeypatch)
    result, checks = _run(cell)
    assert not result["correct"], checks


@pytest.mark.parametrize("fault", ["ae_output_zeroed",
                                   "ae_hidden_perturbed"])
def test_planted_autoencoder_fault_is_not_correct(monkeypatch, fault):
    """The service serves the DCASE autoencoder with broken weights; the
    reference reads the sound ones."""
    from repro.serving.signal_service import SignalService
    _, mod = harness.load_config("dcase2020-t2-ae")
    real = SignalService.register

    def register(self, name, graph, params=None, **k):
        return real(self, name, graph,
                    params=mod.FAULTS[fault](params, 2 ** 31 + 5), **k)
    monkeypatch.setattr(SignalService, "register", register)
    result, checks = _run("dcase-offline")
    assert not result["correct"], checks
    assert checks["score_rel_err"]["value"] > checks["score_rel_err"]["limit"]
