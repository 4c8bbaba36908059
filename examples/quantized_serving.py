"""Variable-bitwidth serving demo: the SigDLA computing array (paper §IV)
as an LLM weight-quantization backend.

- quantize a small LM's weights to int8 / int4 (per-channel symmetric),
- serve batched greedy generations from the engine,
- show that the bitserial Pallas kernel's integer GEMM reproduces the
  dequantized matmul bit-for-bit at the integer level,
- calibrate a whole SignalGraph with SigQuant (repro.precision) and
  serve it under the auto-solved per-step width policy.

    PYTHONPATH=src python examples/quantized_serving.py
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np


def main():
    from repro.configs import get_config
    from repro.core import bitwidth as bw
    from repro.kernels import bitserial_matmul
    from repro.models.zoo import get_model
    from repro.serving import ServingEngine, quantize_tree
    from repro.serving.quantized import quantized_bytes

    cfg = get_config("starcoder2-3b").reduced(
        n_layers=2, d_model=64, n_heads=4, d_ff=128, vocab=512)
    bundle = get_model(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    raw_bytes = sum(l.size * l.dtype.itemsize
                    for l in jax.tree_util.tree_leaves(params))

    prompts = [[5, 6, 7], [100, 101], [7, 8, 9, 10]]
    outs = {}
    for bits in (0, 8, 4):
        eng = ServingEngine(bundle, batch_size=4, quant_bits=bits)
        eng.load(params)
        outs[bits] = eng.generate(prompts, max_new=8)
        if bits:
            q, s = quantize_tree(params, bits, min_size=1024)
            print(f"int{bits}: weight bytes "
                  f"{quantized_bytes(q, s, bits)/1e3:.0f}K"
                  f" (fp {raw_bytes/1e3:.0f}K), "
                  f"greedy tokens match fp: "
                  f"{sum(a == b for a, b in zip(outs[bits], outs[0]))}/3")

    # bitserial kernel == fake-quant reference at the integer level
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((16, 64)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((64, 32)), jnp.float32)
    wq, ws = bw.quantize(w, 4, axis=0)
    xq, xs = bw.quantize(x, 8, axis=-1)
    int_kernel = bitserial_matmul(xq, wq, a_width=8, w_width=4)
    int_ref = np.asarray(xq, np.int64) @ np.asarray(wq, np.int64)
    print("bitserial kernel == integer reference:",
          bool(np.array_equal(np.asarray(int_kernel), int_ref)))
    deq = np.asarray(int_kernel, np.float32) * np.asarray(xs) * np.asarray(ws)
    rel = np.abs(deq - np.asarray(x @ w)).mean() / np.abs(
        np.asarray(x @ w)).mean()
    print(f"dequantized int8x int4 GEMM vs fp32: mean rel err {rel:.3%}")

    # SigQuant: calibrate a whole pipeline, then serve it int-routed
    from repro import precision
    from repro.signal import SignalGraph

    length = 512
    g = SignalGraph("fig9q")
    g.fir("front", "input", taps=np.hanning(9) / np.hanning(9).sum())
    g.stft("spec", "front", frame=64, hop=32)
    g.magnitude("mag", "spec", onesided=False)
    g.dnn_circulant("mask", "mag", 64, block=4,
                    activation=lambda v: jax.nn.sigmoid(v - 1.0))
    g.mul("enh", "spec", "mask")
    g.istft("out", "enh", hop=32, length=length)
    g.outputs("out")
    compiled = g.compile(length, backend="pallas")

    cal = [rng.standard_normal((2, length)).astype(np.float32)
           for _ in range(4)]
    policy, record = precision.auto_policy(compiled, cal, budget=1e-2)
    errs = precision.policy_errors(record, policy)
    print("SigQuant auto policy:",
          {k: f"{a}x{b}" for k, (a, b) in sorted(policy.widths.items())},
          f"held-out rel err {max(errs.values()):.2e}")

    from repro.serving import SignalService
    gs = SignalGraph("fig9q")               # natural-length serving copy
    gs.fir("front", "input", taps=np.hanning(9) / np.hanning(9).sum())
    gs.stft("spec", "front", frame=64, hop=32)
    gs.magnitude("mag", "spec", onesided=False)
    gs.dnn_circulant("mask", "mag", 64, block=4,
                     activation=lambda v: jax.nn.sigmoid(v - 1.0))
    gs.mul("enh", "spec", "mask")
    gs.istft("out", "enh", hop=32)
    gs.outputs("out")
    svc = SignalService(batch_size=4, backend="pallas", precision=policy)
    svc.register("fig9q", gs)
    sess = svc.open_stream("fig9q")
    wave = rng.standard_normal(length).astype(np.float32)
    sess.feed(jnp.asarray(wave))
    svc.stream_step()
    streamed = [sess.read(), sess.close()]
    n = sum(np.asarray(s["out"]).shape[-1] for s in streamed
            if "out" in s)
    print(f"served {n} calibrated samples through "
          f"{svc.backend.name!r} (policy in the compile-cache key)")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
