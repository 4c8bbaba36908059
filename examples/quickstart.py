"""Quickstart: the SigDLA fabric in five minutes.

1. run FFT / FIR / DCT through the programmable shuffle fabric and check
   them against numpy,
2. compile a shuffle plan down to the five-instruction ISA and execute it
   on the cycle-accurate engine,
3. run an exact int8 x int4 GEMM on the variable-bitwidth (bitserial)
   Pallas kernel,
4. build a tiny assigned-architecture LM and take one training step.

    PYTHONPATH=src python examples/quickstart.py
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np


def main():
    rng = np.random.default_rng(0)

    # -- 1. signal processing on the fabric --------------------------------
    from repro import signal as sig
    x = rng.standard_normal(1024) + 1j * rng.standard_normal(1024)
    y = sig.fft(jnp.asarray(x))
    err = np.max(np.abs(np.asarray(y) - np.fft.fft(x)))
    print(f"[1] fabric FFT-1024 vs numpy: max err {err:.2e}")

    h = rng.standard_normal(80)
    xr = rng.standard_normal(256)
    fir = sig.fir_phased(jnp.asarray(xr), jnp.asarray(h), phases=8)
    err = np.max(np.abs(np.asarray(fir) - np.convolve(xr, h)[:256]))
    print(f"[1] multi-phase FIR (all 8 PEs) vs convolve: max err {err:.2e}")

    # -- 2. shuffle plan -> ISA -> cycle-accurate engine --------------------
    from repro.core import fabric
    gi = rng.permutation(32).astype(np.int32)
    gi[[3, 7]] = fabric.PAD
    pv = np.zeros(32, np.int64); pv[3], pv[7] = 1, -1   # DPU constants
    plan = fabric.ShufflePlan(gi, pv, width=8)
    data = rng.integers(-100, 100, 32)
    out, cycles = fabric.apply_plan_via_isa(data, plan)
    ref = fabric.apply_plan_np(data.copy(), plan)
    print(f"[2] ISA execution == plan: {np.array_equal(out, ref)}, "
          f"{cycles.total} cycles "
          f"(rd {cycles.rd_cycles} / cfg {cycles.config_cycles} / "
          f"shuffle {cycles.shuffle_cycles} / wr {cycles.wr_cycles})")

    # -- 3. variable-bitwidth GEMM on the Pallas kernel ---------------------
    from repro.kernels import bitserial_matmul
    a = jnp.asarray(rng.integers(-128, 128, (64, 96)), jnp.int32)
    w = jnp.asarray(rng.integers(-8, 8, (96, 32)), jnp.int32)
    got = bitserial_matmul(a, w, a_width=8, w_width=4)
    exact = bool(np.array_equal(np.asarray(got),
                                np.asarray(a) @ np.asarray(w)))
    print(f"[3] bitserial int8 x int4 GEMM exact: {exact}")

    # -- 4. one train step on a reduced assigned architecture ---------------
    from repro.configs import get_config
    from repro.launch.train import init_train_state, make_train_step
    from repro.models.zoo import get_model

    cfg = get_config("gemma2-2b").reduced()
    bundle = get_model(cfg)
    params, opt = init_train_state(bundle, jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 32),
                                          0, cfg.vocab)}
    step = jax.jit(make_train_step(bundle))
    params, opt, metrics = step(params, opt, batch)
    print(f"[4] gemma2-2b (reduced) train step: loss "
          f"{float(metrics['loss']):.3f}, grad-norm "
          f"{float(metrics['grad_norm']):.3f}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
