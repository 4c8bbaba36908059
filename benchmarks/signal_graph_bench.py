"""Graph-level SigStream benchmark: pipeline lowering at each fusion level.

For each pipeline graph, reports the static fabric-pass / shuffle-word
counts from the graph compiler, the perf-model cycle estimate, and the
measured wall-clock of the jitted compiled callable (CPU here; the ratio
between the variants is the interesting number, mirroring the paper's
shuffle-traffic accounting at pipeline scope).  Variants:

  * ``unfused``   — op-by-op lowering (``fuse=0``);
  * ``fused``     — v1 gather∘gather composition (``fuse=1``);
  * ``fused-v2``  — v1 + cross-einsum permutation folding (``fuse=2``):
    pure-permutation passes ride the array passes' stream-in/out path,
    reported in the ``streamed_words`` column.

A per-**backend** section executes the same compiled programs through
each registered execution backend (``reference`` jnp interpretation vs
``pallas`` fused fabric+array kernels, interpret mode on CPU) and
reports step time plus the lowering report's fused-vs-emulated pass
counts.  ``--compiled`` adds the training-step sweep per backend
binding: the learned Fig-9 forward pass and ``value_and_grad`` step on
``reference``, ``pallas-interpret`` and ``pallas-compiled`` (the Pallas
kernels carry custom VJPs, so the whole step runs on the bound backend;
interpret-only hosts record the compiled rows as ``unsupported``).
``--precision`` adds the SigQuant sweep: the Fig-9 pipeline with a
block-circulant mask layer run fp32, under a uniform 8x8 hand policy,
and under the calibrated auto policy (``repro.precision.auto_policy``) —
reporting int-routed pass counts, end-to-end relative error, and the
width-aware array-cycle estimate.  ``--json PATH`` writes the full table
set as JSON (the CI smoke step uploads it); ``--smoke`` shrinks
sizes/iters for CI.

    PYTHONPATH=src python -m benchmarks.signal_graph_bench [--smoke]
        [--compiled] [--precision]
        [--json artifacts/signal_graph_bench.json]
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time
from typing import List, Tuple

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np


def _bench(fn, *args, iters: int = 10) -> float:
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6   # us


def _graphs(length: int):
    from repro.signal import SignalGraph

    fig9 = SignalGraph("fig9_enhance")
    fig9.stft("spec", frame=256, hop=128)
    fig9.dnn("mask", "spec",
             fn=lambda p, z: jax.nn.sigmoid(jnp.abs(z) - 1.0))
    fig9.mul("enh", "spec", "mask")
    fig9.istft("out", "enh", hop=128, length=length)
    fig9.outputs("out")

    front = SignalGraph("fir_stft_mel")
    front.fir("pre", "input", taps=np.hanning(16) / 8.0)
    front.stft("spec", "pre", frame=256, hop=128)
    front.magnitude("mag", "spec", onesided=True)
    front.mel_filterbank("mel", "mag", sr=16_000, n_mels=40)
    front.outputs("mel")

    return [fig9, front]


VARIANTS = (("fused-v2", 2), ("fused", 1), ("unfused", 0))


def rows(length: int = 4096, batch: int = 4) -> List[Tuple]:
    """(graph, variant, fabric_passes, shuffle_words, streamed_words,
    folded_passes, model_cycles, us_per_call) per graph x
    {fused-v2, fused, unfused}."""
    from repro.core.perf_model import signal_graph_report

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((batch, length)), jnp.float32)
    out = []
    for g in _graphs(length):
        for variant, level in VARIANTS:
            compiled = g.compile(length, fuse=level)
            rep = signal_graph_report(compiled)
            us = _bench(compiled.jit(), x, None)
            out.append((g.name, variant,
                        rep["fabric_passes"], rep["shuffle_words"],
                        rep["streamed_words"], rep["folded_passes"],
                        rep["total"], us))
    return out


HEADER = ("graph,variant,fabric_passes,shuffle_words,streamed_words,"
          "folded_passes,model_cycles,us_per_call")


def format_row(row: Tuple) -> str:
    """One CSV line for a :func:`rows` tuple (kept next to HEADER so the
    column set is defined in exactly one module)."""
    name, variant, passes, words, stream, folded, cycles, us = row
    return (f"{name},{variant},{passes},{words},{stream},{folded},"
            f"{cycles},{us:.1f}")


# -- multi-output SigProgram: shared-prefix reuse vs two single compiles --

def _fig9_multi(length: int, outputs):
    from repro.signal import SignalGraph

    g = SignalGraph("fig9_multi")
    g.stft("spec", frame=256, hop=128)
    g.dnn("mask", "spec",
          fn=lambda p, z: jax.nn.sigmoid(jnp.abs(z) - 1.0))
    g.mul("enh", "spec", "mask")
    g.istft("out", "enh", hop=128, length=length)
    g.magnitude("mag", "enh", onesided=True)
    g.mel_filterbank("mel", "mag", sr=16_000, n_mels=40)
    g.outputs(*outputs)
    return g


MULTI_HEADER = ("graph,variant,fabric_passes,shuffle_words,shared_passes,"
                "us_per_call")


def multi_output_rows(length: int = 4096, batch: int = 4) -> List[Tuple]:
    """One compiled program with outputs('out', 'mel') vs the SAME
    pipeline compiled twice with a single output each: the multi-output
    program lowers the shared prefix (stft -> mask -> mul) once, so its
    pass/word totals and wall clock sit well under the two-compile sum."""
    from repro.core.perf_model import signal_graph_report

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((batch, length)), jnp.float32)
    out = []

    multi = _fig9_multi(length, ("out", "mel")).compile(length)
    rep = signal_graph_report(multi)
    us = _bench(multi.jit(), x, None)
    out.append(("fig9_multi", "multi[out+mel]", rep["fabric_passes"],
                rep["shuffle_words"],
                rep["per_output"]["shared"]["fabric_passes"], us))

    singles = [_fig9_multi(length, (o,)).compile(length)
               for o in ("out", "mel")]
    reps = [signal_graph_report(c) for c in singles]
    us2 = sum(_bench(c.jit(), x, None) for c in singles)
    out.append(("fig9_multi", "2x single",
                sum(r["fabric_passes"] for r in reps),
                sum(r["shuffle_words"] for r in reps), 0, us2))
    return out


# -- execution backends: reference vs pallas on the same programs ---------

BACKENDS = ("reference", "pallas")

BACKEND_HEADER = ("graph,backend,fabric_fused,fabric_emulated,"
                  "array_fused,array_int,array_emulated,us_per_call")


def backend_rows(length: int = 4096, batch: int = 4,
                 iters: int = 10) -> List[Tuple]:
    """(graph, backend, fabric fused/emulated, array fused/int/emulated,
    us_per_call) per graph x backend: the same fuse=2 program bound to
    each execution backend (pallas in interpret mode on CPU — the
    interesting number there is the fused-pass attribution; compiled
    wall-clock needs a real device)."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((batch, length)), jnp.float32)
    out = []
    for g in _graphs(length):
        for backend in BACKENDS:
            compiled = g.compile(length, backend=backend)
            rep = compiled.lowering_report()
            us = _bench(compiled.jit(), x, None, iters=iters)
            out.append((g.name, backend,
                        rep["fabric_passes"]["fused"],
                        rep["fabric_passes"]["emulated"],
                        rep["array_passes"]["fused"],
                        rep["array_passes"]["int_routed"],
                        rep["array_passes"]["emulated"], us))
    return out


GRAD_HEADER = "graph,variant,us_per_step"


def _fig9_learned(length: int):
    from repro.signal import SignalGraph

    g = SignalGraph("fig9_learned")
    taps = np.zeros(9, np.float32)
    taps[0] = 1.0
    g.fir("front", "input", taps=taps)
    g.stft("spec", "front", frame=256, hop=128)
    g.dnn("mask", "spec",
          fn=lambda p, z: jax.nn.sigmoid(jnp.abs(z) - 1.0))
    g.mul("enh", "spec", "mask")
    g.istft("out", "enh", hop=128, length=length)
    g.outputs("out")
    return g


def grad_rows(length: int = 4096, batch: int = 4) -> List[Tuple]:
    """value_and_grad step time of a learned-FIR + dnn-mask Fig-9
    variant (the SigProgram training surface) next to its forward pass."""
    c = _fig9_learned(length).compile(length)
    params = c.init_params()
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((batch, length)), jnp.float32)

    fwd = jax.jit(lambda p, x: c(x, p)["out"])
    us_fwd = _bench(fwd, params, x)

    def loss(outs, target):
        return jnp.mean((outs["out"] - target) ** 2)
    vag = jax.jit(c.value_and_grad(loss, wrt=("front",)))
    us_vag = _bench(vag, params, x, jnp.zeros_like(x))
    return [("fig9_learned", "forward", us_fwd),
            ("fig9_learned", "value_and_grad", us_vag)]


# -- precision sweep: fp32 vs hand policy vs calibrated (SigQuant) --------

PRECISION_HEADER = ("graph,variant,int_routed,max_rel_err,est_cycles,"
                    "us_per_call")


def _fig9_quant(length):
    from repro.signal import SignalGraph

    g = SignalGraph("fig9_quant")
    g.fir("front", "input", taps=np.hanning(9) / np.hanning(9).sum())
    g.stft("spec", "front", frame=64, hop=32)
    g.magnitude("mag", "spec", onesided=False)
    g.dnn_circulant("mask", "mag", 64, block=4,
                    activation=lambda v: jax.nn.sigmoid(v - 1.0))
    g.mul("enh", "spec", "mask")
    g.istft("out", "enh", hop=32, length=length)
    g.outputs("out")
    return g


def _policy_cycles(compiled, policy) -> int:
    """Perf-model estimate of the array-pass cycles under a policy:
    rows x cin x cout MACs per GEMM step over the width-dependent
    ``macs_per_cycle`` throughput ((16, 16) for the float route)."""
    from repro.core import bitwidth as bw

    total = 0
    for e in compiled.einsum_steps():
        widths = policy.widths.get(e.name) if policy is not None else None
        aw, ww = widths if widths is not None else (16, 16)
        macs = e.rows * e.cin * e.cout
        total += int(-(-macs // bw.macs_per_cycle(aw, ww)))
    return total


def precision_rows(length: int = 4096, batch: int = 4,
                   iters: int = 10, budget: float = 1e-2) -> List[Tuple]:
    """(graph, variant, int_routed, max_rel_err, est_cycles, us_per_call)
    for the Fig-9 enhancement pipeline with its mask as a block-circulant
    layer: ``fp32`` (no policy), ``hand`` (uniform 8x8 on every GEMM
    step), and ``calibrated`` (the SigQuant auto policy at ``budget``)."""
    from repro import precision as pz
    from repro.signal.backends import PallasBackend

    g = _fig9_quant(length)
    c = g.compile(length, backend="pallas")
    rng = np.random.default_rng(0)
    cal = [rng.standard_normal((batch, length)).astype(np.float32)
           for _ in range(4)]
    policy, record = pz.auto_policy(c, cal, budget=budget)
    from repro.signal.backends import PrecisionPolicy
    hand = PrecisionPolicy(widths={s: (8, 8) for s in policy.widths})

    x = jnp.asarray(rng.standard_normal((batch, length)), jnp.float32)
    fref = np.asarray(g.compile(length)(x)["out"])
    out = []
    for variant, pol in (("fp32", None), ("hand", hand),
                         ("calibrated", policy)):
        be = PallasBackend() if pol is None else PallasBackend(precision=pol)
        cq = c.with_backend(be)
        got = np.asarray(cq(x)["out"])
        err = float(np.linalg.norm(got - fref) /
                    max(np.linalg.norm(fref), 1e-12))
        us = _bench(cq.jit(), x, None, iters=iters)
        out.append((g.name, variant,
                    cq.lowering_report()["array_passes"]["int_routed"],
                    err, _policy_cycles(cq, pol), us))
    return out


# -- compiled-mode sweep: the training step per backend binding -----------

COMPILED_HEADER = "graph,backend_mode,direction,us,note"


def compiled_rows(length: int = 4096, batch: int = 4,
                  iters: int = 10) -> List[Tuple]:
    """(graph, backend_mode, direction, us, note): the learned Fig-9
    forward pass and full ``value_and_grad`` step on ``reference``,
    ``pallas-interpret`` and ``pallas-compiled`` bindings.  Pallas now
    carries custom VJPs, so the gradient step runs on the bound backend
    with no re-bind; on interpret-only hosts the compiled rows are
    recorded as ``unsupported`` rather than dropped."""
    from repro.kernels import compiled_supported
    from repro.signal.backends import PallasBackend

    g = _fig9_learned(length)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((batch, length)), jnp.float32)
    target = jnp.zeros_like(x)

    def loss(outs, tgt):
        return jnp.mean((outs["out"] - tgt) ** 2)

    can_compile = compiled_supported()
    modes = [("reference", "reference", True),
             ("pallas-interpret", PallasBackend(interpret=True), True),
             ("pallas-compiled", PallasBackend(interpret=False),
              can_compile)]
    out = []
    for mode, backend, supported in modes:
        if not supported:
            for direction in ("forward", "value_and_grad"):
                out.append(("fig9_learned", mode, direction, float("nan"),
                            "unsupported: jax backend is interpret-only"))
            continue
        c = g.compile(length, backend=backend)
        params = c.init_params()
        fwd = jax.jit(lambda p, xx: c(xx, p)["out"])
        out.append(("fig9_learned", mode, "forward",
                    _bench(fwd, params, x, iters=iters), ""))
        vag = jax.jit(c.value_and_grad(loss, wrt=("front",)))
        out.append(("fig9_learned", mode, "value_and_grad",
                    _bench(vag, params, x, target, iters=iters), ""))
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: small sizes, few iters, hard asserts")
    ap.add_argument("--compiled", action="store_true",
                    help="add the per-backend-binding training-step "
                         "sweep (reference / pallas-interpret / "
                         "pallas-compiled, forward + value_and_grad)")
    ap.add_argument("--precision", action="store_true",
                    help="add the SigQuant sweep: fp32 vs uniform hand "
                         "policy vs calibrated auto policy (error + "
                         "estimated array cycles per variant)")
    ap.add_argument("--json", type=str, default=None,
                    help="write all tables as JSON to this path")
    args = ap.parse_args(argv)
    length = 1024 if args.smoke else 4096
    batch = 2 if args.smoke else 4
    iters = 3 if args.smoke else 10

    fusion = rows(length, batch)
    print(HEADER)
    for row in fusion:
        print(format_row(row))
    print()
    backend = backend_rows(length, batch, iters)
    print(BACKEND_HEADER)
    for name, be, ff, fe, af, ai, ae, us in backend:
        print(f"{name},{be},{ff},{fe},{af},{ai},{ae},{us:.1f}")
    if args.smoke:
        # the pallas backend must actually fuse the array passes (and
        # at least one fabric pass) on the Fig-9 pipeline — a lowering
        # regression fails CI here, not just in unit tests.
        by = {(r[0], r[1]): r for r in backend}
        for g in {r[0] for r in backend}:
            assert by[(g, "pallas")][4] > 0, f"{g}: no fused array passes"
            assert by[(g, "reference")][4] == 0
        assert by[("fig9_enhance", "pallas")][2] >= 1, \
            "fig9: framing gather should fuse into the butterfly kernel"
    print()
    multi = multi_output_rows(length, batch)
    print(MULTI_HEADER)
    for name, variant, passes, words, shared, us in multi:
        print(f"{name},{variant},{passes},{words},{shared},{us:.1f}")
    print()
    grad = grad_rows(length, batch)
    print(GRAD_HEADER)
    for name, variant, us in grad:
        print(f"{name},{variant},{us:.1f}")

    precision = []
    if args.precision:
        print()
        precision = precision_rows(length, batch, iters)
        print(PRECISION_HEADER)
        for name, variant, n_int, err, cycles, us in precision:
            print(f"{name},{variant},{n_int},{err:.2e},{cycles},{us:.1f}")
        if args.smoke:
            by = {r[1]: r for r in precision}
            # the auto policy must cover every GEMM step and hold the
            # budget — a solver or observer regression fails CI here.
            assert by["fp32"][2] == 0
            assert by["calibrated"][2] == by["hand"][2] > 0
            assert by["calibrated"][3] <= 1e-2
            # narrowing must pay: fewer estimated array cycles than fp32
            assert by["calibrated"][4] < by["fp32"][4]

    compiled = []
    if args.compiled:
        print()
        compiled = compiled_rows(length, batch, iters)
        print(COMPILED_HEADER)
        for name, mode, direction, us, note in compiled:
            print(f"{name},{mode},{direction},{us:.1f},{note}")
        if args.smoke:
            # pallas-interpret must run the full training step — a
            # rebind regression (or a lost VJP rule) fails CI here.
            measured = {r[1] for r in compiled if not np.isnan(r[3])}
            assert {"reference", "pallas-interpret"} <= measured
            from repro.kernels import compiled_supported
            if compiled_supported():
                assert "pallas-compiled" in measured

    if args.json:
        from repro.core.perf_model import PERF_SCHEMA_VERSION
        payload = {
            "schema_version": 1,
            "perf_model_schema_version": PERF_SCHEMA_VERSION,
            "fusion": [dict(zip(HEADER.split(","), r)) for r in fusion],
            "backends": [dict(zip(BACKEND_HEADER.split(","), r))
                         for r in backend],
            "multi_output": [dict(zip(MULTI_HEADER.split(","), r))
                             for r in multi],
            "grad": [dict(zip(GRAD_HEADER.split(","), r)) for r in grad],
            "precision": [dict(zip(PRECISION_HEADER.split(","), r))
                          for r in precision],
            "compiled": [dict(zip(COMPILED_HEADER.split(","),
                                  (*r[:3], None if np.isnan(r[3]) else r[3],
                                   r[4])))
                         for r in compiled],
        }
        path = pathlib.Path(args.json)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(payload, indent=2))
        print(f"\nwrote {path}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
