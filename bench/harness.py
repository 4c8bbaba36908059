"""Runs one cell of ``BENCHMARK.json`` once and builds the result line.

Everything is found by name: a workload names a configuration
(``bench/configs/<config>.json`` with its code and plain reference in
``<config>.py``) and a traffic mix (``bench/traffic/<traffic>.json``);
each per-layer metric is read by ``bench/metrics/<name>.py``, or by the
reader named by the part of its name before the first dot
(``step_mfu.offline`` -> ``step_mfu.py``).
"""

from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
from typing import Dict, Optional

import numpy as np

from bench import drive, peaks

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


class Refused(Exception):
    """The run cannot measure here (no TPU, too few chips, no program)."""


# -- finding things by name ---------------------------------------------------

def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def workload(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def _module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_config(name: str):
    """``(cfg, module)`` of a configuration."""
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        cfg = json.load(f)
    return cfg, _module(os.path.join(BENCH, "configs", f"{name}.py"),
                        f"bench_config_{name.replace('-', '_')}")


def load_traffic(name: str) -> dict:
    with open(os.path.join(BENCH, "traffic", f"{name}.json")) as f:
        return json.load(f)


def metric_reader(name: str):
    """The ``read(run)`` function of a per-layer metric."""
    for stem in (name, name.split(".")[0]):
        path = os.path.join(BENCH, "metrics", f"{stem}.py")
        if os.path.exists(path):
            return _module(path, f"bench_metric_{stem}").read
    raise KeyError(f"no reader for metric {name!r} under bench/metrics/")


def metrics_for(spec: dict, cell: str, kind: str):
    """The entries of ``spec[kind]`` that the cell reports."""
    return [m for m in spec[kind]
            if "workloads" not in m or cell in m["workloads"]]


# -- the device ---------------------------------------------------------------

def device_info(chips: int, require_tpu: bool = True) -> dict:
    import jax
    devs = jax.devices()
    if require_tpu:
        if devs[0].platform != "tpu":
            raise Refused(f"no TPU: JAX found {devs[0].platform!r} devices "
                          f"({devs[0].device_kind}); the benchmark has no "
                          f"fallback")
        from repro.kernels import interpret_default
        if interpret_default():
            raise Refused("Pallas kernels would run in interpret mode")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def memory_peak(chips: int) -> int:
    import jax
    peak = 0
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


# -- correctness ----------------------------------------------------------------

def rel_err(got, want) -> float:
    """max |got - want| / max |want|, or inf on a shape mismatch or a
    non-finite value."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return float("inf")
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                   1e-30))


def rel_rms(got, want) -> float:
    """rms(got - want) / rms(want), or inf on a shape mismatch or a
    non-finite value."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return float("inf")
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / max(np.sqrt(np.mean(want ** 2)), 1e-30))


NUMBERS = {"rel_err": rel_err, "rel_rms": rel_rms}


def check(cfg, mod, params, answers, failed, control=False) -> Dict:
    """Each output's worst reading of each number over the answers
    against the plain reference, beside its limit (``cfg["limits"]``:
    output -> number -> limit); and the failed count (limit 0).
    ``control`` (``True``, or ``"model"`` for the model alone) puts the
    reference one precision lower in the program's place."""
    worst = {(o, n): 0.0 for o, nums in cfg["limits"].items() for n in nums}
    for _, x, got in answers:
        want = mod.reference(cfg, params, x)
        if control:
            got = mod.reference(cfg, params, x, control=control)
        for o, n in worst:
            v = NUMBERS[n](got[o], want[o]) if o in got else float("inf")
            worst[o, n] = max(worst[o, n], v)
    checks = {f"{o}_{n}": {"value": v, "limit": cfg["limits"][o][n]}
              for (o, n), v in worst.items()}
    checks["failed"] = {"value": failed, "limit": 0}
    return checks


def passes(checks: Dict, n_answers: int) -> bool:
    """Every number within its limit, over at least one answer."""
    return n_answers > 0 and all(c["value"] <= c["limit"]
                                 for c in checks.values())


# -- one run ------------------------------------------------------------------------

def build(wl: dict, seed: int, cfg_override: Optional[dict] = None,
          traffic_override: Optional[dict] = None,
          fault: Optional[str] = None):
    """The cell's configuration, module, mix, seeded params and the
    service under test with its graph registered.  ``fault`` names one of
    the configuration's ``FAULTS``, planted in the weights the service
    gets (not in the params returned, which the reference reads)."""
    from repro.serving import SignalService

    cfg, mod = load_config(wl["config"])
    cfg.update(cfg_override or {})
    traffic = dict(load_traffic(wl["traffic"]), **(traffic_override or {}))
    params = mod.make_params(cfg, seed)
    served = mod.FAULTS[fault](params, seed) if fault else params
    svc = SignalService(**cfg["service"])
    svc.register(cfg["name"], mod.build_graph(cfg), params=served)
    return cfg, mod, traffic, params, svc


def run(wl: dict, spec: dict, seed: int, seconds: float, trace: bool,
        t_process: float, require_tpu: bool = True,
        traffic_override: Optional[dict] = None,
        cfg_override: Optional[dict] = None, compile_cache: bool = True):
    """One run of one cell.  Returns ``(result, notes)``; the result's
    ``metrics`` are the end-to-end metrics, or with ``trace`` the
    per-layer ones, and its ``checks`` come last."""
    dev = device_info(wl["chips"], require_tpu)
    import jax
    from repro.compile_cache import enable_compile_cache

    cache = enable_compile_cache() if compile_cache else None
    cfg, mod, traffic, params, svc = build(wl, seed, cfg_override,
                                           traffic_override)

    def start_trace():
        if trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            jax.profiler.start_trace(TRACE_DIR)

    def stop_trace():
        if trace:
            jax.profiler.stop_trace()

    cell = drive.Cell(svc, cfg["name"], cfg, mod, traffic, seed, seconds,
                      t_process, trace, start_trace, stop_trace,
                      drive.CompileCounter())
    rec = drive.MODES[traffic["mode"]](cell)
    dev["memory_peak_bytes"] = memory_peak(wl["chips"])
    notes = dict(rec.notes, compile_cache=cache, window_s=rec.window_s)
    del svc, cell

    host_params = jax.tree_util.tree_map(np.asarray, params)
    checks = check(cfg, mod, host_params, rec.answers, rec.failed)
    notes["answers_checked"] = len(rec.answers)
    result = {"correct": passes(checks, len(rec.answers)),
              "attempted": rec.attempted, "failed": rec.failed,
              "metrics": {}, "device": dev}
    if not trace:
        for m in metrics_for(spec, wl["name"], "end_to_end"):
            result["metrics"][m["name"]] = {"value": rec.e2e[m["name"]],
                                            "unit": m["unit"]}
    else:
        from bench import trace as tr
        from bench.metrics import RunData
        t = tr.load(TRACE_DIR)
        summ = tr.summary(t)
        dev["busy_s"] = summ["busy_s"]
        dev["window_s"] = summ["window_s"]
        data = RunData(cfg=cfg, mod=mod, record=rec, trace=t,
                       peaks=peaks.for_device(dev["kind"]))
        for m in metrics_for(spec, wl["name"], "per_layer"):
            v = metric_reader(m["name"])(data)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        result["breakdown"] = summ["breakdown"]
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    result["checks"] = checks
    return result, notes


def main(argv, t_process: float) -> int:
    import argparse
    ap = argparse.ArgumentParser(prog="bench/run.py",
                                 description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = load_benchmark()
    wl = workload(spec, args.workload)
    try:
        result, notes = run(wl, spec, args.seed, args.seconds,
                            bool(args.trace), t_process)
    except Refused as e:
        print(f"bench: refused: {e}", file=sys.stderr)
        return 2
    for k, v in notes.items():
        print(f"note {k}: {v}", flush=True)
    sys.stdout.flush()
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
