"""The paper's Fig 9 pipeline as a served **SigProgram**:

    noisy speech -> learned FIR front-end -> STFT (fabric FFT)
                 -> CNN mask -> masked spectrum -> iSTFT -> enhanced
                                          `-> mel monitoring tap

The pipeline is declared once as a :class:`repro.signal.SignalGraph`
with TWO named outputs — ``outputs("out", "mel_tap")`` — and compiled to
one fused shuffle-plan + einsum program whose shared prefix (front-end,
STFT, mask, masked spectrum) is lowered once; the perf report attributes
the per-output passes.  The same compiled program is then:

  1. trained end to end through ``compiled.value_and_grad`` — the FIR
     front-end taps AND the mask CNN both live in the params pytree and
     both receive gradients through the fabric lowering,
  2. executed in streaming chunks (enhanced stream bit-identical to
     offline; the mel tap streams per block within the documented
     FIR-GEMM ULP caveat),
  3. served through a SignalService with per-output results, co-scheduled
     with an LLM ServingEngine on one step loop — the paper's concurrent
     DSP+DL story.

``--backend pallas`` runs every phase — training included — through the
fused fabric+array kernels: the shuffle-GEMM ops carry custom VJPs, so
``value_and_grad`` differentiates the Pallas lowering directly instead
of re-binding to the reference interpreter.

    PYTHONPATH=src python examples/speech_enhancement.py [--steps 40]
    PYTHONPATH=src python examples/speech_enhancement.py --smoke   # CI
    PYTHONPATH=src python examples/speech_enhancement.py --smoke \
        --backend pallas                  # train on the array kernels
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

FRAME, HOP = 256, 128


# -- mask CNN (streams bit-exactly: lax.conv windows are position-invariant)

def init_cnn(key, ch=(2, 12, 12, 1)):
    ks = jax.random.split(key, len(ch) - 1)
    return [
        (jax.random.normal(k, (3, 3, ci, co)) * (1.0 / np.sqrt(9 * ci)))
        for k, ci, co in zip(ks, ch[:-1], ch[1:])
    ]


def cnn_mask(params, spec):
    """Complex spectrum (B, T, F) -> sigmoid mask (B, T, F)."""
    mag = jnp.abs(spec)
    x = jnp.stack([jnp.log1p(mag), jnp.cos(jnp.angle(spec))], axis=-1)
    squeeze = x.ndim == 3
    if squeeze:
        x = x[None]
    for i, w in enumerate(params):
        x = jax.lax.conv_general_dilated(
            x, w, (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        if i < len(params) - 1:
            x = jax.nn.gelu(x)
    m = jax.nn.sigmoid(x[..., 0])
    return m[0] if squeeze else m


def build_graph(length, ch=(2, 12, 12, 1), fir_taps=9, n_mels=24):
    """The Fig-9 SigProgram: learned-FIR front-end, mask CNN, enhanced
    stream plus a mel monitoring tap — one graph, two named outputs."""
    from repro.core.perf_model import ConvLayer
    from repro.signal import SignalGraph

    n_frames = 1 + (length - FRAME) // HOP
    g = SignalGraph("speech_enhancement")
    # learnable front-end: starts as a delta (identity) filter
    taps0 = np.zeros(fir_taps, np.float32)
    taps0[0] = 1.0
    g.fir("front", "input", taps=taps0)
    g.stft("spec", "front", frame=FRAME, hop=HOP)
    # 3x3 convs over (frames, bins): receptive field len(ch)-1 frames each
    # side; declare the actual layers so signal_graph_report covers the
    # DNN's array cycles too.
    layers = [ConvLayer(f"mask_conv{i}", h=n_frames, w=FRAME, k=3,
                        cin=ci, cout=co)
              for i, (ci, co) in enumerate(zip(ch[:-1], ch[1:]))]
    g.dnn("mask", "spec", fn=cnn_mask, frame_context=len(ch) - 1,
          layers=layers)
    g.mul("enh", "spec", "mask")
    g.istft("out", "enh", hop=HOP, length=length)
    # monitoring tap: mel energies of the enhanced spectrum, streamed
    # alongside the audio from the SAME compiled program.
    g.magnitude("mag", "enh", onesided=True)
    g.mel_filterbank("mel_tap", "mag", sr=16_000, n_mels=n_mels)
    g.outputs("out", "mel_tap")
    return g


def snr_db(clean, x):
    num = jnp.sum(clean ** 2, -1)
    den = jnp.sum((x - clean) ** 2, -1) + 1e-9
    return 10.0 * jnp.log10(num / den)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--length", type=int, default=4096)
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: few steps, small model, hard asserts")
    ap.add_argument("--trace", type=str, default=None,
                    help="record a SigTrace chrome-trace of the serving "
                         "phase to this path (REPRO_TRACE=... also works)")
    ap.add_argument("--backend", type=str, default="reference",
                    help="execution backend for every phase, training "
                         "included ('reference' or 'pallas')")
    args = ap.parse_args()
    if args.smoke:
        args.steps, args.batch, args.length = 6, 2, 2048
    length = args.length

    from repro import obs
    if args.trace:
        obs.enable(trace_path=args.trace)
    else:
        obs.enable_from_env()

    from repro.core.perf_model import signal_graph_report
    from repro.data import SignalStream
    from repro.serving import (CoScheduler, Request, ServingEngine,
                               SignalRequest, SignalService)
    from repro.signal import FuseLevel, StreamingRunner

    graph = build_graph(length)
    fused = graph.compile(length, fuse=FuseLevel.STREAM,
                          backend=args.backend)
    assert fused.backend.differentiable, args.backend
    rep = signal_graph_report(fused)
    rep_u = signal_graph_report(graph.compile(length, fuse=FuseLevel.NONE))
    print(f"fabric passes : fused {rep['fabric_passes']:3d}   "
          f"unfused {rep_u['fabric_passes']:3d}")
    shared = rep["per_output"]["shared"]
    print("per-output    : " + "  ".join(
        f"{name}={rep['per_output'][name]['fabric_passes']}p"
        for name in rep["outputs"])
        + f"  shared={shared['fabric_passes']}p (lowered once)")

    # -- train front-end + mask end to end via compiled.value_and_grad ----
    stream = SignalStream(length=length, global_batch=args.batch, seed=0)
    params = dict(fused.init_params())         # front taps (+ mel weights)
    params["mask"] = init_cnn(jax.random.PRNGKey(0))

    def loss_fn(outs, clean):
        edge = FRAME
        return jnp.mean((outs["out"][:, edge:-edge]
                         - clean[:, edge:-edge]) ** 2)

    vag = jax.jit(fused.value_and_grad(loss_fn, wrt=("front", "mask")))

    # AdamW on the trainable subset of the params pytree (front taps +
    # mask CNN); the frozen entries (mel weights) ride along untouched.
    from repro.optim.adamw import adamw_init, adamw_update
    trainable = ("front", "mask")
    opt_state = adamw_init({k: params[k] for k in trainable})

    @jax.jit
    def apply(p, g, opt):
        sub = {k: p[k] for k in trainable}
        sub, opt, _ = adamw_update(g, opt, sub, lr=1e-2, weight_decay=0.0)
        return {**p, **sub}, opt

    b0 = stream.batch_at(10_000)
    noisy0 = jnp.asarray(b0["noisy"]); clean0 = jnp.asarray(b0["clean"])
    snr_noisy = float(jnp.mean(snr_db(clean0[:, FRAME:-FRAME],
                                      noisy0[:, FRAME:-FRAME])))
    run = fused.jit()
    # before/after loss on ONE held-out batch — a true reduction check
    # that holds even at --steps 1
    eval_loss_before, _ = vag(params, noisy0, clean0)
    for i in range(args.steps):
        b = stream.batch_at(i)
        l, grads = vag(params, jnp.asarray(b["noisy"]),
                       jnp.asarray(b["clean"]))
        params, opt_state = apply(params, grads, opt_state)
        if i % 20 == 0:
            print(f"step {i:4d} loss {float(l):.4f}")
    eval_loss_after, _ = vag(params, noisy0, clean0)
    assert float(eval_loss_after) < float(eval_loss_before), \
        "training must reduce the held-out loss"

    out1 = run(noisy0, params)
    snr_after = float(jnp.mean(snr_db(clean0[:, FRAME:-FRAME],
                                      out1["out"][:, FRAME:-FRAME])))
    print(f"\ninput SNR         : {snr_noisy:6.2f} dB")
    print(f"enhanced (trained): {snr_after:6.2f} dB")
    if not args.smoke:                     # smoke runs too few steps for SNR
        assert snr_after > snr_noisy, "enhancement must beat the noisy input"

    # -- streaming: chunked per-output execution vs the offline run -------
    runner = StreamingRunner(graph, params=params, backend=args.backend)
    cuts = [length // 8, length // 3, length // 2 + 300]
    acc = {}
    for c in np.split(np.asarray(noisy0), cuts, axis=-1):
        for k, v in runner.process(jnp.asarray(c)).items():
            acc.setdefault(k, []).append(np.asarray(v))
    for k, v in runner.flush().items():
        acc.setdefault(k, []).append(np.asarray(v))
    streamed = np.concatenate(acc["out"], axis=-1)
    # the learned-FIR front-end streams ULP-close (im2col GEMM row counts
    # differ per chunk); everything downstream is the same math.
    exact = np.allclose(streamed, np.asarray(out1["out"]), atol=1e-5)
    mel_stream = np.concatenate(acc["mel_tap"], axis=-2)
    mel_ok = np.allclose(mel_stream, np.asarray(out1["mel_tap"]),
                         rtol=1e-4, atol=1e-4)
    print(f"streamed out ~= offline: {exact}   mel tap ~=: {mel_ok}")
    assert exact and mel_ok
    lat = runner.struct.output_latencies()
    print("latencies     : " + "  ".join(
        f"{k}={v['latency']} {v['domain']}" for k, v in lat.items()))

    # -- streaming sessions: 2 connections, one jitted core call per tick
    service = SignalService(batch_size=args.batch, block_frames=8,
                            backend=args.backend)
    service.register("speech_enhancement", graph, params=params)
    sessions = [service.open_stream("speech_enhancement") for _ in range(2)]
    sess_out = [{} for _ in sessions]
    chunk = 512
    for lo in range(0, length, chunk):
        for k, s in enumerate(sessions):
            s.feed(jnp.asarray(np.asarray(noisy0[k, lo:lo + chunk])))
        service.stream_step()
        for k, s in enumerate(sessions):
            for name, v in s.read().items():
                sess_out[k].setdefault(name, []).append(v)
    for k, s in enumerate(sessions):
        for name, v in s.close().items():
            sess_out[k].setdefault(name, []).append(v)
    sess_ok = all(
        np.allclose(np.concatenate(sess_out[k]["out"], axis=-1),
                    np.asarray(out1["out"][k]), atol=1e-5)
        and np.allclose(np.concatenate(sess_out[k]["mel_tap"], axis=-2),
                        np.asarray(out1["mel_tap"][k]),
                        rtol=1e-4, atol=1e-4)
        for k in range(2))
    print(f"{len(sess_out)} stream sessions (out + mel_tap) ~= offline: "
          f"{sess_ok} ({service.stats['core_calls']} batched core calls)")
    assert sess_ok

    # -- serve mixed-length DSP requests co-scheduled with LLM decode -----
    from repro.configs import get_config
    from repro.models.zoo import get_model
    cfg = get_config("starcoder2-3b").reduced(
        n_layers=2, d_model=32, n_heads=4, d_ff=64, vocab=128)
    bundle = get_model(cfg)
    engine = ServingEngine(bundle, batch_size=2)
    engine.load(bundle.init(jax.random.PRNGKey(1)))

    sched = CoScheduler(engine, service, policy="cost_balanced")
    lengths = [length - 500 - 200 * i for i in range(args.batch)]
    for i, t in enumerate(lengths):            # mixed lengths, one bucket
        sched.submit_signal(SignalRequest(
            rid=100 + i, graph="speech_enhancement",
            samples=np.asarray(noisy0[i % noisy0.shape[0], :t])))
        sched.submit_llm(Request(rid=i, prompt=[i + 1, i + 2], max_new=8))
    llm, dsp = sched.run()
    assert all(set(r) == {"out", "mel_tap"} for r in dsp.values())
    occ = sched.occupancy()
    print(f"co-scheduled {len(llm)} LLM + {len(dsp)} mixed-length DSP "
          f"requests (per-output results) in {sched.ticks} ticks "
          f"({service.stats['compiles']} bucket compiles, "
          f"dsp share {occ['dsp_share']:.2f})")
    if obs.ENABLED:
        path = obs.get_tracer().export(obs.default_trace_path())
        stats = obs.validate_trace(path)
        print(obs.render_report(obs.build_report(scheduler=sched)))
        print(f"wrote trace {path} ({stats['events']} events)")
    print("OK: SigProgram — multi-output, trained, streamed, served")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
