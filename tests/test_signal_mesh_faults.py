"""SigMesh fault tolerance: sharded SignalService parity on a forced
8-device mesh, device loss mid-stream with bit-identical resumed output,
retry/rollback and retry-exhaustion -> durable checkpoint restore +
journal replay (StreamSupervisor), straggler detection, and DecodeWave
snapshot/resume.

Multi-device tests run in subprocesses (tests/_mesh_helpers.py — the
forced device count must be set before jax imports); supervisor logic is
device-count-agnostic and runs in the main process on a *virtual*
8-shard :class:`SignalMesh` (logical shards wrap round-robin over the
single CPU device, so routing / affinity / checkpoint paths are the
same code).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _mesh_helpers import last_json
from repro.runtime import DeviceLoss, StepMonitor, StreamSupervisor
from repro.serving import DecodeWave, Request, SignalService
from repro.signal import SignalGraph

T = 1024


def _mask(p, z):
    return jax.nn.sigmoid(jnp.abs(z) - 1.0)


def _fig9(name="fig9"):
    g = SignalGraph(name)
    g.stft("spec", frame=256, hop=128)
    g.dnn("mask", "spec", fn=_mask)
    g.mul("enh", "spec", "mask")
    g.istft("out", "enh", hop=128)
    g.outputs("out")
    return g


def _run_stream(svc, w, chunk=512, injector=None, sup_kw=None):
    """Feed ``w`` in chunks through one supervised session; returns the
    concatenated read()/close() stream and the supervisor."""
    sup = StreamSupervisor(svc, **(sup_kw or {}))
    sess = svc.open_stream("fig9")
    pieces = []
    empty = np.zeros(0, np.float32)
    for lo in range(0, len(w), chunk):
        sup.feed(sess, jnp.asarray(w[lo:lo + chunk]))
        sup.tick(injector)
        pieces.append(sess.read().get("out", empty))
    pieces.append(sess.close().get("out", empty))
    return np.concatenate(pieces, axis=-1), sup


def _reference_stream(w, chunk=512):
    svc = SignalService(batch_size=4)
    svc.register("fig9", _fig9())
    out, _ = _run_stream(svc, w, chunk)
    return out


# --------------------------------------------------------------------------
# Main-process supervisor contract (virtual 8-shard mesh, 1 CPU device)
# --------------------------------------------------------------------------

def test_transient_failure_rolls_back_and_retries_bit_identical():
    rng = np.random.default_rng(0)
    w = rng.standard_normal(4 * T).astype(np.float32)
    ref = _reference_stream(w)

    svc = SignalService(batch_size=4, mesh=8)
    svc.register("fig9", _fig9())
    fired = []

    def injector(tick, attempt):
        if tick == 2 and attempt == 0:
            fired.append(tick)
            raise RuntimeError("transient device error")

    out, sup = _run_stream(svc, w, injector=injector)
    assert fired == [2]
    np.testing.assert_array_equal(ref, out)
    assert sup.stats["retries"] == 1
    assert sup.stats["checkpoint_restores"] == 0


def test_retry_exhaustion_restores_durable_checkpoint_and_replays():
    rng = np.random.default_rng(1)
    w = rng.standard_normal(4 * T).astype(np.float32)
    ref = _reference_stream(w)

    svc = SignalService(batch_size=4, mesh=8)
    svc.register("fig9", _fig9())
    attempts = []

    def injector(tick, attempt):
        # persistent failure at tick 3: fails attempt 0..max_retries,
        # forcing the durable restore + journal replay path, then the
        # replacement node comes up clean (attempt resets to 0 and the
        # flag below stops further raises)
        if tick == 3 and len(attempts) <= 2:
            attempts.append(attempt)
            raise RuntimeError("persistent device error")

    out, sup = _run_stream(svc, w, injector=injector,
                           sup_kw={"ckpt_every": 2, "max_retries": 2})
    assert attempts == [0, 1, 2]
    np.testing.assert_array_equal(ref, out)
    assert sup.stats["checkpoint_restores"] == 1
    assert sup.stats["retries"] == 3


def test_straggler_hook_fires_on_slow_tick():
    rng = np.random.default_rng(2)
    w = rng.standard_normal(2 * T).astype(np.float32)
    svc = SignalService(batch_size=4, mesh=8)
    svc.register("fig9", _fig9())
    slow = []
    # factor 0: every tick after the first EWMA sample is a "straggler"
    out, sup = _run_stream(
        svc, w,
        sup_kw={"monitor": StepMonitor(straggler_factor=0.0),
                "on_straggler": lambda tick, dt: slow.append(tick)})
    assert slow, "straggler hook never fired"
    assert sup.monitor.stragglers == slow


def test_restore_detaches_sessions_opened_after_checkpoint():
    svc = SignalService(batch_size=4, mesh=8)
    svc.register("fig9", _fig9())
    ck = svc.checkpoint()
    sess = svc.open_stream("fig9")
    svc.restore(ck)
    assert sess.closed and "checkpoint" in sess.error
    with pytest.raises(ValueError):
        sess.feed(np.zeros(256, np.float32))
    assert svc.stats["detached_sessions"] == 1


# --------------------------------------------------------------------------
# DecodeWave checkpoint (LLM side of the co-scheduled service)
# --------------------------------------------------------------------------

def _tiny_engine(temperature=0.0):
    from repro.configs import get_config
    from repro.models.zoo import get_model
    from repro.serving import ServingEngine
    cfg = get_config("starcoder2-3b").reduced(
        n_layers=2, d_model=32, n_heads=4, d_ff=64, vocab=128)
    bundle = get_model(cfg)
    eng = ServingEngine(bundle, batch_size=2, temperature=temperature)
    eng.load(bundle.init(jax.random.PRNGKey(0)))
    return eng


def test_decode_wave_snapshot_resumes_identical_tokens():
    eng = _tiny_engine()
    reqs = [Request(rid=0, prompt=[1, 2, 3], max_new=6),
            Request(rid=1, prompt=[4, 5], max_new=6)]
    ref = DecodeWave(eng, [Request(rid=r.rid, prompt=list(r.prompt),
                                   max_new=r.max_new) for r in reqs])
    wave = DecodeWave(eng, reqs)
    for _ in range(3):
        ref.step()
        wave.step()
    snap = wave.snapshot()
    resumed = DecodeWave.from_snapshot(eng, snap)
    while not ref.done:
        ref.step()
    while not resumed.done:
        resumed.step()
    assert resumed.results() == ref.results()


def test_decode_wave_snapshot_requires_greedy():
    eng = _tiny_engine(temperature=0.7)
    wave = DecodeWave(eng, [Request(rid=0, prompt=[1, 2], max_new=3)])
    with pytest.raises(ValueError, match="greedy"):
        wave.snapshot()


# --------------------------------------------------------------------------
# Forced 8-device subprocess tests (real placement)
# --------------------------------------------------------------------------

def test_sharded_service_bit_identical_on_8_devices(forced_mesh):
    """Bucketed one-shot serving (even and uneven = masked buckets) and
    multi-output stream sessions produce bit-identical results sharded
    over 8 real (forced host) devices vs the unsharded service, and the
    per-device occupancy ledger sees every shard."""
    out = forced_mesh("""
        import jax, jax.numpy as jnp, numpy as np, json
        from repro.serving import SignalService, SignalRequest, SignalMesh
        from repro.signal import SignalGraph

        def mask(p, z):
            return jax.nn.sigmoid(jnp.abs(z) - 1.0)

        def fig9(name="f"):
            g = SignalGraph(name)
            g.stft("spec", frame=256, hop=128)
            g.dnn("mask", "spec", fn=mask)
            g.mul("enh", "spec", "mask")
            g.istft("out", "enh", hop=128)
            g.magnitude("mag", "enh", onesided=True)
            g.mel_filterbank("mel", "mag", sr=16_000, n_mels=8)
            g.outputs("out", "mel")
            return g

        n_dev = len(jax.devices())
        rng = np.random.default_rng(0)
        # uneven lengths share a bucket -> masked execution over pad rows
        lens = [1024, 1024, 900, 700, 1024, 800, 640]
        sigs = [rng.standard_normal(n).astype(np.float32) for n in lens]
        reqs = lambda: [SignalRequest(rid=i, graph="f", samples=s)
                        for i, s in enumerate(sigs)]

        ref = SignalService(batch_size=4)
        ref.register("f", fig9())
        svc = SignalService(batch_size=4, mesh=SignalMesh(8))
        svc.register("f", fig9())
        r0, r1 = ref.serve(reqs()), svc.serve(reqs())
        serve_match = sorted(r0) == sorted(r1) and all(
            np.array_equal(r0[i]["out"], r1[i]["out"])
            and np.array_equal(r0[i]["mel"], r1[i]["mel"]) for i in r0)

        # multi-output stream sessions, device-affinity routed
        w = [rng.standard_normal(3 * 1024).astype(np.float32)
             for _ in range(4)]
        def drain(service):
            sessions = [service.open_stream("f") for _ in range(4)]
            got = [{"out": [], "mel": []} for _ in sessions]
            for lo in range(0, 3 * 1024, 512):
                for s, wi in zip(sessions, w):
                    s.feed(jnp.asarray(wi[lo:lo + 512]))
                service.stream_step()
                for g, s in zip(got, sessions):
                    for k, v in s.read().items():
                        g[k].append(v)
            for g, s in zip(got, sessions):
                for k, v in s.close().items():
                    g[k].append(v)
            # unbatched sessions: "out" is 1-D samples, "mel" pieces
            # concatenate along their leading frames axis
            axes = {"out": -1, "mel": 0}
            return [{k: np.concatenate(v, axis=axes[k])
                     for k, v in g.items()} for g in got], sessions

        g0, _ = drain(ref)
        g1, sessions = drain(svc)
        stream_match = all(
            np.array_equal(a["out"], b["out"])
            and np.array_equal(a["mel"], b["mel"])
            for a, b in zip(g0, g1))
        occ = svc.router.occupancy()
        print(json.dumps({
            "n_dev": n_dev,
            "serve_match": bool(serve_match),
            "stream_match": bool(stream_match),
            "session_devices": [s.device_index for s in sessions],
            "busy_devices": sum(1 for c in occ["device_cycles"] if c > 0),
            "wall_lt_est": bool(svc.wall_cycles < svc.est_cycles),
        }))
    """)
    r = last_json(out)
    assert r["n_dev"] == 8
    assert r["serve_match"] and r["stream_match"]
    # least-loaded routing spreads the 4 sessions over 4 distinct shards
    assert len(set(r["session_devices"])) == 4
    assert r["busy_devices"] == 8
    # the sharded wall clock beats the offered-work clock
    assert r["wall_lt_est"]


def test_device_loss_mid_stream_resumes_bit_identical_on_8_devices(
        forced_mesh):
    """Killing the shard a session is homed on mid-stream degrades to a
    restored, replayed, bit-identical stream on the surviving shards."""
    out = forced_mesh("""
        import jax, jax.numpy as jnp, numpy as np, json
        from repro.runtime import DeviceLoss, StreamSupervisor
        from repro.serving import SignalService, SignalMesh
        from repro.signal import SignalGraph

        def mask(p, z):
            return jax.nn.sigmoid(jnp.abs(z) - 1.0)

        def fig9():
            g = SignalGraph("f")
            g.stft("spec", frame=256, hop=128)
            g.dnn("mask", "spec", fn=mask)
            g.mul("enh", "spec", "mask")
            g.istft("out", "enh", hop=128)
            g.outputs("out")
            return g

        rng = np.random.default_rng(7)
        w = rng.standard_normal(5 * 1024).astype(np.float32)

        def drain(service, injector=None):
            sup = StreamSupervisor(service, ckpt_every=2)
            sess = service.open_stream("f")
            pieces, devices = [], []
            empty = np.zeros(0, np.float32)
            for lo in range(0, len(w), 512):
                sup.feed(sess, jnp.asarray(w[lo:lo + 512]))
                sup.tick(None if injector is None
                         else (lambda t, a: injector(sess, t, a)))
                pieces.append(sess.read().get("out", empty))
                devices.append(sess.device_index)
            pieces.append(sess.close().get("out", empty))
            return np.concatenate(pieces, axis=-1), sup, devices

        ref = SignalService(batch_size=4)
        ref.register("f", fig9())
        expected, _, _ = drain(ref)

        svc = SignalService(batch_size=4, mesh=SignalMesh(8))
        svc.register("f", fig9())
        state = {"fired": False}

        def injector(sess, tick, attempt):
            if tick == 4 and not state["fired"]:
                state["fired"] = True
                raise DeviceLoss(sess.device_index)

        got, sup, devices = drain(svc, injector)
        print(json.dumps({
            "match": bool(np.array_equal(expected, got)),
            "fired": state["fired"],
            "device_losses": sup.stats["device_losses"],
            "alive": svc.router.alive_count(),
            "moved": len(set(devices)) > 1,
            "restores": sup.stats["checkpoint_restores"],
        }))
    """)
    r = last_json(out)
    assert r["fired"] and r["device_losses"] == 1
    assert r["alive"] == 7
    assert r["moved"], "session never re-homed off the dead shard"
    assert r["restores"] >= 1
    assert r["match"], "resumed stream is not bit-identical"


def test_pallas_service_runs_row_parallel_on_4_devices(forced_mesh):
    """The pallas backend on a 4-device mesh: bucket calls run as a
    row-split shard_map (XLA cannot partition a Mosaic kernel), every
    device computes its own rows, and the results match the unsharded
    pallas service."""
    out = forced_mesh("""
        import jax, jax.numpy as jnp, numpy as np, json
        from repro.serving import SignalService, SignalRequest, SignalMesh
        from repro.signal import SignalGraph

        def mask(p, z):
            return jax.nn.sigmoid(jnp.abs(z) - 1.0)

        g = SignalGraph("f")
        g.fir("front", "input", taps=np.hanning(5) / 2.0)
        g.stft("spec", "front", frame=256, hop=128)
        g.dnn("mask", "spec", fn=mask)
        g.mul("enh", "spec", "mask")
        g.istft("out", "enh", hop=128)
        g.magnitude("mag", "enh", onesided=True)
        g.mel_filterbank("mel", "mag", sr=16_000, n_mels=8)
        g.outputs("out", "mel")

        rng = np.random.default_rng(3)
        lens = [1024, 900, 700, 1024, 640]
        sigs = [rng.standard_normal(n).astype(np.float32) for n in lens]
        reqs = lambda: [SignalRequest(rid=i, graph="f", samples=s)
                        for i, s in enumerate(sigs)]
        one = SignalService(batch_size=8, backend="pallas")
        one.register("f", g)
        svc = SignalService(batch_size=8, backend="pallas",
                            mesh=SignalMesh(4))
        svc.register("f", g)
        r0, r1 = one.serve(reqs()), svc.serve(reqs())
        err = max(float(np.max(np.abs(r0[i][k] - r1[i][k])))
                  for i in r0 for k in ("out", "mel"))

        fn = svc.mesh.row_parallel(lambda x, p: x * p, 1)
        y = fn(svc.mesh.shard(np.ones((8, 4), np.float32)), 2.0)
        print(json.dumps({
            "err": err,
            "keys": sorted(r0) == sorted(r1),
            "row_axis": y.sharding.spec[0],
            "y_devices": len(y.sharding.device_set),
            "busy": sum(1 for c in svc.router.occupancy()["device_cycles"]
                        if c > 0),
        }))
    """, devices=4)
    r = last_json(out)
    assert r["keys"]
    # per-shard programs see 2 rows where the unsharded one sees 8; XLA
    # may order float32 reductions differently, so allow a few ULPs of
    # outputs of order 1
    assert r["err"] < 1e-5, r
    assert r["row_axis"] == "data" and r["y_devices"] == 4
    assert r["busy"] == 4


def test_mesh_sweep_refuses_accelerator_hosts(monkeypatch):
    """The bench's --mesh sweep times forced CPU host devices; on a TPU
    host it must refuse rather than report CPU numbers as the mesh's."""
    from benchmarks import signal_service_bench as bench
    monkeypatch.setattr(bench.jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="forced CPU host devices"):
        bench.run_mesh_sweep([1])
