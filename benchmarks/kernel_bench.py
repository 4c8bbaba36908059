"""Wall-time microbenchmarks of the fabric-mapped signal ops and kernels
(jitted JAX on this host's CPU — for harness completeness; TPU numbers
come from the roofline, not from this box).

``--compiled`` adds the compiled-mode kernel sweep: per gather∘einsum
group size, the fused shuffle-GEMM kernel under ``interpret=True``
(:func:`repro.kernels.interpret_default` on CPU), under
``interpret=False`` (real Pallas lowering — recorded as ``unsupported``
on hosts whose jax backend is interpret-only), and the XLA-fused
reference (``apply_plan`` + ``jnp`` matmul), forward AND VJP.  The
``compiled-kernels`` CI lane runs ``--compiled --smoke --json`` and the
result lands in ``BENCH_PR8.json`` via ``benchmarks/trajectory.py``.

    PYTHONPATH=src python -m benchmarks.kernel_bench [--smoke]
        [--compiled] [--json artifacts/kernel_bench.json]
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time
from typing import Callable, List, Tuple

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np


def _bench(fn: Callable, *args, iters: int = 20) -> float:
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6   # us


def rows() -> List[Tuple[str, float, str]]:
    from repro import signal as sig
    from repro.core import bitwidth as bw
    from repro.kernels import bitserial_matmul

    rng = np.random.default_rng(0)
    out = []

    for n in (256, 1024, 4096):
        z = jnp.asarray(rng.standard_normal((8, n))
                        + 1j * rng.standard_normal((8, n)),
                        dtype=jnp.complex64)
        f = jax.jit(lambda x: sig.fft(x))
        us = _bench(f, z)
        ref = jax.jit(jnp.fft.fft)
        us_ref = _bench(ref, z)
        out.append((f"fabric_fft{n}_b8", us, f"vs jnp.fft {us_ref:.0f}us"))

    x = jnp.asarray(rng.standard_normal((8, 4096)), jnp.float32)
    h = jnp.asarray(rng.standard_normal(80), jnp.float32)
    out.append(("fabric_fir4096_t80", _bench(jax.jit(sig.fir), x, h), ""))
    out.append(("fabric_fir_phased8", _bench(
        jax.jit(lambda a, b: sig.fir_phased(a, b, 8)), x, h), ""))

    xs = jnp.asarray(rng.standard_normal((4, 16384)), jnp.float32)
    out.append(("stft_16k_f256", _bench(
        jax.jit(lambda a: sig.stft(a, 256, 128)), xs), ""))

    a = jnp.asarray(rng.integers(-128, 128, (256, 512)), jnp.int32)
    w = jnp.asarray(rng.integers(-8, 8, (512, 256)), jnp.int32)
    out.append(("bitserial_mm_8x4_256", _bench(
        lambda: bitserial_matmul(a, w, 8, 4)), "interpret-mode pallas"))
    out.append(("plane_matmul_8x4_256", _bench(
        jax.jit(lambda aa, ww: bw.plane_matmul(aa, ww, 8, 4)), a, w), ""))
    return out


# -- compiled-mode sweep: interpret vs compiled vs XLA reference ----------

COMPILED_HEADER = "group,mode,direction,us,note"

# (rows, t, n_out, grouped?) — gather∘einsum group sizes spanning the
# shapes the backend actually emits: FIR-tap rows (n_out=1), mel-sized
# GEMMs, and one FFT-butterfly grouped shape.
_COMPILED_SIZES = [
    ("gemm_r256_t16_o8", 256, 16, 8),
    ("gemm_r1024_t9_o1", 1024, 9, 1),
    ("gemm_r512_t64_o40", 512, 64, 40),
]
_COMPILED_SIZES_SMOKE = _COMPILED_SIZES[:2]


def _group_case(rows: int, t: int, n_out: int, seed: int = 0):
    """One synthetic gather∘einsum group: a duplicating (im2col-like)
    plan over an input half the gathered volume, plus operand + batch."""
    from repro.core.fabric import ShufflePlan

    rng = np.random.default_rng(seed)
    n_in = max(rows * t // 2, t)
    gi = ((np.arange(rows * t) * 7) % n_in).astype(np.int32)
    plan = ShufflePlan(gi, np.zeros(rows * t, np.float64))
    diag = rng.standard_normal(rows * t).astype(np.float32)
    x = jnp.asarray(rng.standard_normal((4, n_in)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((t, n_out)), jnp.float32)
    return plan, diag, x, w


def compiled_rows(smoke: bool = False,
                  iters: int = 10) -> List[Tuple[str, str, str, float, str]]:
    """(group, mode, direction, us, note) per group size x
    {interpret, compiled, xla_ref} x {forward, vjp}.  ``compiled`` rows
    on interpret-only hosts carry ``us = nan`` and an ``unsupported``
    note instead of failing — the sweep is green-but-honest."""
    from repro.core.fabric import apply_plan
    from repro.kernels import compiled_supported, shuffle_gemm

    out: List[Tuple[str, str, str, float, str]] = []
    sizes = _COMPILED_SIZES_SMOKE if smoke else _COMPILED_SIZES
    can_compile = compiled_supported()
    for name, rows_, t, n_out in sizes:
        plan, diag, x, w = _group_case(rows_, t, n_out)

        def kernel_fn(interpret):
            return jax.jit(lambda x, w: shuffle_gemm(
                x, plan, w, rows=rows_, interpret=interpret, diag=diag))

        def xla_fn():
            def f(x, w):
                g = apply_plan(x, plan) * jnp.asarray(diag)
                return g.reshape(*g.shape[:-1], rows_, t) @ w
            return jax.jit(f)

        modes = [("interpret", lambda: kernel_fn(True), True),
                 ("compiled", lambda: kernel_fn(False), can_compile),
                 ("xla_ref", xla_fn, True)]
        for mode, make, supported in modes:
            if not supported:
                out.append((name, mode, "forward", float("nan"),
                            "unsupported: jax backend is interpret-only"))
                out.append((name, mode, "vjp", float("nan"),
                            "unsupported: jax backend is interpret-only"))
                continue
            fn = make()
            us_fwd = _bench(fn, x, w, iters=iters)
            vjp = jax.jit(jax.grad(
                lambda x, w: jnp.sum(fn(x, w) ** 2), argnums=(0, 1)))
            us_vjp = _bench(vjp, x, w, iters=iters)
            out.append((name, mode, "forward", us_fwd, ""))
            out.append((name, mode, "vjp", us_vjp, ""))
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: small sizes, few iters")
    ap.add_argument("--compiled", action="store_true",
                    help="add the compiled-vs-interpret-vs-XLA sweep "
                         "(forward + VJP per group size)")
    ap.add_argument("--json", type=str, default=None,
                    help="write all tables as JSON to this path")
    args = ap.parse_args(argv)

    kernels = [] if args.smoke else rows()
    if kernels:
        print("name,us,note")
        for name, us, note in kernels:
            print(f"{name},{us:.1f},{note}")
        print()

    compiled = []
    if args.compiled:
        from repro.kernels import compiled_supported
        compiled = compiled_rows(smoke=args.smoke,
                                 iters=3 if args.smoke else 10)
        print(COMPILED_HEADER)
        for group, mode, direction, us, note in compiled:
            print(f"{group},{mode},{direction},{us:.1f},{note}")
        if args.smoke:
            # interpret + xla_ref rows must exist for fwd AND vjp; the
            # compiled rows must be either measured or honestly marked.
            by_mode = {}
            for r in compiled:
                by_mode.setdefault(r[1], []).append(r)
            assert len(by_mode["interpret"]) == len(by_mode["xla_ref"])
            for r in by_mode["compiled"]:
                assert (not np.isnan(r[3])) or "unsupported" in r[4]
            assert ("unsupported" in by_mode["compiled"][0][4]) \
                != compiled_supported()

    if args.json:
        payload = {
            "schema_version": 1,
            "kernels": [dict(zip(("name", "us", "note"), r))
                        for r in kernels],
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
