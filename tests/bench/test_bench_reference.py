"""The configurations' graphs, as the service runs them, against their
plain numpy references at small sizes on the CPU; and the control (the
reference one precision lower), which the limits must refuse."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import arith, harness


def _served(cfg, mod, params, xs, backend):
    """Exact-length requests through the service (one wave)."""
    from repro.serving import SignalRequest, SignalService
    svc = SignalService(batch_size=len(xs), backend=backend,
                        buckets=[len(xs[0])])
    svc.register(cfg["name"], mod.build_graph(cfg), params=params)
    return svc.serve([SignalRequest(rid=i, graph=cfg["name"], samples=x)
                      for i, x in enumerate(xs)])


@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_dcase_graph_matches_numpy_reference(backend):
    cfg, mod = harness.load_config("dcase2020-t2-ae")
    n = 8192                                 # 15 frames, 11 vectors
    params = mod.make_params(cfg, 2 ** 31 + 3)
    xs = [mod.make_audio(cfg, arith.np_rng(4, i), n) for i in range(2)]
    with jax.default_matmul_precision("highest"):
        got = _served(cfg, mod, params, xs, backend)
    host = jax.tree_util.tree_map(np.asarray, params)
    for i, x in enumerate(xs):
        want = mod.reference(cfg, host, x)
        assert got[i]["score"].shape == (15, 1)
        assert np.all(got[i]["score"][-4:] == 0)
        for k in ("score", "logmel"):
            assert harness.rel_err(got[i][k], want[k]) < 1e-5, k
        clip = float(np.mean(want["score"][:11]))
        assert clip == pytest.approx(float(np.mean(got[i]["score"][:11])),
                                     rel=1e-5)


def test_fig9_graph_matches_numpy_reference():
    cfg, mod = harness.load_config("fig9-speech-enhance")
    n = 2048
    params = mod.make_params(cfg, 9)
    xs = [mod.make_audio(cfg, arith.np_rng(5, i), n) for i in range(2)]
    with jax.default_matmul_precision("highest"):
        got = _served(cfg, mod, params, xs, "reference")
    host = jax.tree_util.tree_map(np.asarray, params)
    for i, x in enumerate(xs):
        want = mod.reference(cfg, host, x)
        assert got[i]["out"].shape == ((1 + (n - 256) // 128 - 1) * 128
                                       + 256,)
        for k in ("out", "mel_tap"):
            assert harness.rel_err(got[i][k], want[k]) < 1e-5, k


@pytest.mark.parametrize("name,n", [("fig9-speech-enhance", 4096),
                                    ("dcase2020-t2-ae", 16384)])
def test_control_fails_the_limits(name, n):
    """The reference computed one precision lower (signal stages in
    bfloat16, model products in float8), put in the program's place,
    must come out not correct."""
    cfg, mod = harness.load_config(name)
    params = jax.tree_util.tree_map(np.asarray, mod.make_params(cfg, 1))
    answers = [(f"request {i}", mod.make_audio(cfg, arith.np_rng(6, i), n),
                None) for i in range(3)]
    checks = harness.check(cfg, mod, params, answers, 0, control=True)
    assert not harness.passes(checks, len(answers))
    worst = max(c["value"] / c["limit"] for k, c in checks.items()
                if k != "failed")
    assert worst > 1.0


def test_autoencoder_control_fails_the_limits():
    """The DCASE reference with the autoencoder's products alone in
    float8, the signal stages in float64, must come out not correct: the
    score depends on the model, not on the log-mel alone."""
    cfg, mod = harness.load_config("dcase2020-t2-ae")
    params = jax.tree_util.tree_map(np.asarray, mod.make_params(cfg, 2))
    answers = [(f"clip {i}", mod.make_audio(cfg, arith.np_rng(7, i), 16384),
                None) for i in range(3)]
    checks = harness.check(cfg, mod, params, answers, 0, control="model")
    assert checks["logmel_rel_err"]["value"] == 0.0
    assert checks["score_rel_err"]["value"] > checks["score_rel_err"]["limit"]


def test_rel_err_refuses_wrong_shapes_and_nan():
    assert harness.rel_err(np.ones(3), np.ones(4)) == float("inf")
    assert harness.rel_err(np.array([np.nan, 1.0]), np.ones(2)) \
        == float("inf")
    assert harness.rel_err(np.array([1.0, 2.5]), np.array([1.0, 2.0])) \
        == pytest.approx(0.25)
