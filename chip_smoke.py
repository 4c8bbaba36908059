"""Chip smoke test: the Fig-9 speech-enhancement SigProgram served on the
``pallas`` backend beside an LLM at its published widths, on one TPU.

    python chip_smoke.py             # one chip: DSP serving + co-serving
    python chip_smoke.py --mesh 4    # four chips: SigMesh row sharding only

One process, seeded, no downloads.  The phases run in order and any
failure exits non-zero:

1. Device gate: a TPU backend, compiled (not interpreted) Pallas kernels.
2. The persistent compile cache (``repro.compile_cache``).
3. DSP serving at deployment size: ``SignalService(batch_size=8,
   backend="pallas")`` answers 16 one-shot requests of 16,000-65,536
   samples (the 16,384, 32,768 and 65,536 buckets) and two streaming
   sessions fed 512-sample chunks for 2 s of audio; every output is held
   to a float32 reference (``backend="reference"`` at "highest" matmul
   precision) within the tolerances of ``TOLERANCES``.
4. Co-serving: ``CoScheduler(engine, service, "cost_balanced")`` runs the
   same DSP requests beside 4 greedy starcoder2-3b requests (30 layers,
   d_model 3072, vocabulary 49,152, random weights from the seed); the
   co-scheduled tokens must equal ``ServingEngine.generate``'s.

With ``--mesh N`` only the sharded phase runs: the same DSP requests and
sessions through ``SignalService(mesh=N)`` and the unsharded service,
compared under the same tolerances, on N distinct chips that all carry
rows.  The last stdout line is the JSON result; timings on earlier lines
are information only.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), os.path.join(ROOT, "examples")]

import numpy as np

SEED = 0
SAMPLE_RATE = 16_000
BUCKET = 65_536                 # the largest serving bucket, about 4 s
BATCH = 8                       # SignalService rows per bucket call
BUCKETS = (16_384, 32_768, 65_536)
MIN_LEN = 16_000                # shortest one-shot request, 1 s
N_REQUESTS = 16
N_SESSIONS = 2
CHUNK = 512                     # 32 ms of audio per streamed chunk
STREAM_LEN = 2 * SAMPLE_RATE    # 2 s per session
LLM_ARCH = "starcoder2-3b"
LLM_REQUESTS = 4
MAX_NEW = 16

# Per-output tolerance on max|served - reference| / max|reference|, per
# request and per session.  The reference runs every matmul and conv in
# float32 ("highest").  The served program runs its Pallas GEMMs at
# float32 too, but XLA convolutions at the TPU's default precision take
# one bf16 pass (8-bit mantissa, relative error 2^-9 per product).
TOLERANCES = {
    # the mask CNN's 3x3 convs (2->12->12->1 channels) run at bf16: an
    # input rounding of 2^-9 perturbs the logits by ~1e-3, the sigmoid
    # (slope <= 1/4) passes less than that to the mask, and the
    # enhanced spectrum and its iSTFT scale with it — 1e-2 leaves 10x
    # margin, while a wrong kernel or plan errs by O(1).
    "out": 1e-2,
    # mel energies are a float32 GEMM over the same masked magnitudes,
    # so they inherit the mask's relative error and nothing else.
    "mel_tap": 1e-2,
}


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def gate(n_chips: int):
    """Refuse to run anywhere but on ``n_chips`` TPUs with compiled
    Pallas kernels."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        fail(f"no TPU: JAX found only {platform!r} devices "
             f"({devices[0].device_kind}); this smoke test measures the "
             f"chip and has no CPU fallback")
    if len(devices) < n_chips:
        fail(f"needs {n_chips} TPU chips, found {len(devices)}")
    from repro.kernels import interpret_default
    if interpret_default():          # REPRO_PALLAS_INTERPRET asks for it
        fail("Pallas kernels would run in interpret mode (REPRO_PALLAS_"
             f"INTERPRET={os.environ.get('REPRO_PALLAS_INTERPRET')!r})")
    print(f"device: {devices[0].device_kind} x{len(devices)} "
          f"({platform}), jax {jax.__version__}", flush=True)
    return devices


def make_inputs(seed: int = SEED, n_requests: int = N_REQUESTS,
                n_sessions: int = N_SESSIONS, buckets=BUCKETS,
                min_len: int = MIN_LEN, stream_len: int = STREAM_LEN):
    """Seeded noisy multi-sine audio: one-shot requests whose lengths
    cycle through the buckets (each drawn inside its bucket's range),
    and one signal per streaming session."""
    from repro.data import SignalStream

    rng = np.random.default_rng(seed)
    bounds = [min_len - 1] + list(buckets)
    lengths = [int(rng.integers(bounds[i % len(buckets)] + 1,
                                bounds[i % len(buckets) + 1] + 1))
               for i in range(n_requests)]
    audio = SignalStream(length=max(buckets[-1], stream_len),
                         global_batch=n_requests + n_sessions,
                         fs=float(SAMPLE_RATE), seed=seed).batch_at(0)
    noisy = audio["noisy"]
    requests = [noisy[i, :n] for i, n in enumerate(lengths)]
    streams = [noisy[n_requests + k, :stream_len]
               for k in range(n_sessions)]
    return requests, streams


def build_program(length: int = BUCKET, seed: int = SEED):
    """The Fig-9 graph of examples/speech_enhancement.py and its seeded
    params (compile-time defaults plus the mask CNN)."""
    import jax
    from speech_enhancement import build_graph, init_cnn

    graph = build_graph(length)
    params = dict(graph.compile(length).init_params())
    params["mask"] = init_cnn(jax.random.PRNGKey(seed))
    return graph, params


def serve_dsp(service, requests, streams, chunk: int = CHUNK):
    """One-shot requests through ``service.serve`` and streaming sessions
    through ``stream_step``; returns ``(one_shot, streamed)`` with
    streamed outputs concatenated per session."""
    from repro.serving import SignalRequest

    one_shot = service.serve([
        SignalRequest(rid=i, graph="fig9", samples=x)
        for i, x in enumerate(requests)])
    sessions = [service.open_stream("fig9") for _ in streams]
    got = [{} for _ in sessions]
    for lo in range(0, max(len(s) for s in streams), chunk):
        for sess, x in zip(sessions, streams):
            if lo < len(x):
                sess.feed(x[lo:lo + chunk])
        service.stream_step()
        for g, sess in zip(got, sessions):
            for k, v in sess.read().items():
                g.setdefault(k, []).append(v)
    for g, sess in zip(got, sessions):
        for k, v in sess.close().items():
            g.setdefault(k, []).append(v)
    # 1-D sessions: "out" pieces run along time, "mel_tap" along frames
    axis = {"out": -1, "mel_tap": 0}
    streamed = [{k: np.concatenate(v, axis=axis[k]) for k, v in g.items()}
                for g in got]
    return one_shot, streamed, sessions


def compare(tag: str, got, want) -> dict:
    """Hold each output to its tolerance; returns the worst error per
    output."""
    worst = {}
    for key in want:
        for name, tol in TOLERANCES.items():
            a, b = np.asarray(got[key][name]), np.asarray(want[key][name])
            if a.shape != b.shape:
                fail(f"{tag} {key} {name}: shape {a.shape} != {b.shape}")
            if not np.all(np.isfinite(a)):
                fail(f"{tag} {key} {name}: non-finite values")
            err = float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)),
                                                    1e-30))
            worst[name] = max(worst.get(name, 0.0), err)
            if err > tol:
                fail(f"{tag} {key} {name}: relative error {err:.3e} > "
                     f"{tol:g}")
    print(f"{tag}: " + "  ".join(f"{n} max rel err {e:.3e} (tol {TOLERANCES[n]:g})"
                                 for n, e in worst.items()), flush=True)
    return worst


def as_keyed(one_shot, streamed):
    out = {f"request {rid}": r for rid, r in one_shot.items()}
    out.update({f"session {k}": s for k, s in enumerate(streamed)})
    return out


def dsp_phase(graph, params, requests, streams):
    """Phase 3: the pallas service against the float32 reference.
    Returns the pallas service (its buckets stay compiled for phase 4)
    and the reference's one-shot results."""
    import jax
    from repro.kernels import resolve_interpret
    from repro.serving import SignalService

    t0 = time.perf_counter()
    ref = SignalService(batch_size=BATCH, backend="reference")
    ref.register("fig9", graph, params=params)
    with jax.default_matmul_precision("highest"):
        ref_one, ref_streamed, _ = serve_dsp(ref, requests, streams)
    print(f"reference (float32) served in {time.perf_counter() - t0:.1f}s",
          flush=True)

    t0 = time.perf_counter()
    svc = SignalService(batch_size=BATCH, backend="pallas")
    svc.register("fig9", graph, params=params)
    if resolve_interpret(svc.backend.interpret):
        fail("the pallas backend resolved to interpret mode")
    one, streamed, _ = serve_dsp(svc, requests, streams)
    print(f"pallas served {len(one)} requests + {len(streamed)} sessions "
          f"in {time.perf_counter() - t0:.1f}s (compiles included; "
          f"{svc.stats['compiles']} bucket compiles, "
          f"{svc.stats['core_calls']} stream core calls)", flush=True)
    compare("pallas vs float32 reference", as_keyed(one, streamed),
            as_keyed(ref_one, ref_streamed))
    return svc, ref_one


def check_kernels_in_program(svc, params, bucket: int = BUCKET):
    """The bucket program the service runs holds the Pallas kernels as
    TPU custom calls; print its route table."""
    import jax
    import jax.numpy as jnp

    compiled = svc.compiled_for("fig9", bucket)
    routes = compiled.lowering_report()["routes"]
    print("routes: " + "  ".join(f"{k}={routes.get(k, 0)}"
                                 for k in ("fused_gemm", "fused_grouped",
                                           "jnp", "host")), flush=True)
    x = jax.ShapeDtypeStruct((BATCH, bucket), jnp.float32)
    vf = jax.ShapeDtypeStruct((BATCH,), jnp.int32)
    text = compiled.masked_jit().lower(x, vf, params).as_text()
    n = text.count("tpu_custom_call")
    if n == 0:
        fail("the lowered bucket program holds no tpu_custom_call")
    print(f"bucket {bucket}: {n} tpu_custom_call sites", flush=True)


def llm_phase(svc, requests, ref_one, seed: int = SEED, cfg=None):
    """Phase 4: co-serve the DSP requests with the LLM; tokens must equal
    ``generate``'s and DSP results the float32 reference's."""
    import jax
    from repro.configs import get_config
    from repro.models.zoo import get_model
    from repro.serving import (CoScheduler, Request, ServingEngine,
                               SignalRequest)

    cfg = cfg or get_config(LLM_ARCH)
    t0 = time.perf_counter()
    bundle = get_model(cfg)
    engine = ServingEngine(bundle, batch_size=LLM_REQUESTS)
    engine.load(jax.jit(bundle.init)(jax.random.PRNGKey(seed)))
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(engine.params))
    print(f"{cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"vocab {cfg.vocab}, {n_params / 1e9:.2f}B params, init in "
          f"{time.perf_counter() - t0:.1f}s", flush=True)

    rng = np.random.default_rng(seed + 1)
    prompts = [rng.integers(1, cfg.vocab, int(n)).tolist()
               for n in rng.integers(8, 33, LLM_REQUESTS)]
    logits, _, _ = engine.prefill_prompts(prompts, MAX_NEW)
    logits = np.asarray(logits)
    if logits.shape != (LLM_REQUESTS, 1, cfg.padded_vocab) \
            or not np.all(np.isfinite(logits)):
        fail(f"prefill logits: shape {logits.shape}, finite "
             f"{bool(np.all(np.isfinite(logits)))}")
    t0 = time.perf_counter()
    want = engine.generate(prompts, max_new=MAX_NEW)
    print(f"generate: {LLM_REQUESTS} x {MAX_NEW} tokens in "
          f"{time.perf_counter() - t0:.1f}s (compile included)", flush=True)

    t0 = time.perf_counter()
    sched = CoScheduler(engine, svc, policy="cost_balanced")
    # every LLM request is queued before the first tick, so one wave
    # holds them all and no mid-flight admission re-prefills it
    for i, p in enumerate(prompts):
        sched.submit_llm(Request(rid=i, prompt=p, max_new=MAX_NEW))
    for i, x in enumerate(requests):
        sched.submit_signal(SignalRequest(rid=i, graph="fig9", samples=x))
    llm, dsp = sched.run()
    print(f"co-scheduled {len(llm)} LLM + {len(dsp)} DSP requests in "
          f"{sched.ticks} ticks, {time.perf_counter() - t0:.1f}s, dsp "
          f"share {sched.occupancy()['dsp_share']:.3f} (model cycles)",
          flush=True)
    for i, toks in enumerate(want):
        got = llm.get(i)
        if got is None or len(got) != MAX_NEW \
                or not all(0 <= t < cfg.padded_vocab for t in got):
            fail(f"LLM request {i}: bad token list {got}")
        if got != toks:
            fail(f"LLM request {i}: co-scheduled tokens {got} != "
                 f"generate's {toks}")
    compare("co-served DSP vs float32 reference", dsp, ref_one)


def mesh_phase(n_chips: int, graph, params, requests, streams):
    """``--mesh N``: the row-sharded service against the unsharded one."""
    from repro.serving import SignalService

    t0 = time.perf_counter()
    one = SignalService(batch_size=BATCH, backend="pallas")
    one.register("fig9", graph, params=params)
    base_one, base_streamed, _ = serve_dsp(one, requests, streams)
    print(f"unsharded pallas served in {time.perf_counter() - t0:.1f}s",
          flush=True)

    t0 = time.perf_counter()
    svc = SignalService(batch_size=BATCH, backend="pallas", mesh=n_chips)
    svc.register("fig9", graph, params=params)
    got_one, got_streamed, sessions = serve_dsp(svc, requests, streams)
    print(f"mesh={n_chips} pallas served in {time.perf_counter() - t0:.1f}s",
          flush=True)
    compare(f"mesh={n_chips} vs unsharded",
            as_keyed(got_one, got_streamed),
            as_keyed(base_one, base_streamed))

    devices = svc.mesh.devices
    ids = {d.id for d in devices}
    if len(ids) != n_chips or any(d.platform != "tpu" for d in devices):
        fail(f"mesh spans {sorted(ids)} ({[d.platform for d in devices]}),"
             f" not {n_chips} distinct TPU chips")
    occ = svc.router.occupancy()
    if not all(c > 0 for c in occ["device_cycles"]):
        fail(f"not every shard carried rows: {occ['device_cycles']}")
    print(f"mesh devices {sorted(ids)}; rows charged per shard "
          f"{occ['device_cycles']} (model cycles); sessions on shards "
          f"{[s.device_index for s in sessions]}", flush=True)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mesh", type=int, default=0,
                    help="run only the SigMesh phase on this many chips")
    args = ap.parse_args(argv)
    n_chips = args.mesh or 1
    devices = gate(n_chips)

    from repro.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    t_start = time.perf_counter()
    graph, params = build_program()
    requests, streams = make_inputs()
    print(f"inputs: {len(requests)} requests of {min(map(len, requests))}-"
          f"{max(map(len, requests))} samples, {len(streams)} sessions of "
          f"{STREAM_LEN} samples in {CHUNK}-sample chunks", flush=True)
    if args.mesh:
        mesh_phase(n_chips, graph, params, requests, streams)
    else:
        svc, ref_one = dsp_phase(graph, params, requests, streams)
        check_kernels_in_program(svc, params)
        llm_phase(svc, requests, ref_one)
    print(f"total {time.perf_counter() - t_start:.1f}s", flush=True)
    print(json.dumps({"ok": True,
                      "device": {"platform": devices[0].platform,
                                 "kind": devices[0].device_kind,
                                 "count": len(devices)}}))


if __name__ == "__main__":
    main()
