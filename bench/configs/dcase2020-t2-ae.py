"""DCASE 2020 Challenge Task 2 baseline (unsupervised anomalous sound
detection for machine condition monitoring;
github.com/y-kawagu/dcase2020_task2_baseline, ``baseline.yaml``) as a
SignalGraph: power spectrum (n_fft 1024, hop 512) -> 128 HTK mels ->
10 log10 -> 5 frames stacked into 640-dim vectors -> dense autoencoder
640-128-128-128-128-8-128-128-128-128-640 -> mean squared reconstruction
error per vector.

Outputs: ``logmel`` (frames, 128) and ``score`` (frames, 1), where row
``t`` holds the error of the vector of frames ``t .. t+4`` and the last
four rows, which start no whole vector, are 0.  A clip's anomaly score
is the mean of its first ``frames - 4`` rows.
"""

from __future__ import annotations

import numpy as np

from bench import arith

LOG_FLOOR = float(np.finfo(np.float64).eps)   # the source's epsilon
BN_EPS = 1e-3               # Keras BatchNormalization's epsilon
CALIBRATION_STREAM = 6      # the seed's stream of the calibration clip


# -- the program side: graph, weights, audio --------------------------------

def _logmel(params, x):
    import jax.numpy as jnp
    return 10.0 * jnp.log10(x + LOG_FLOOR)


def _make_score(n_stack):
    def score(params, x):
        """Stack ``n_stack`` frames, run the autoencoder, mean squared
        error per vector; rows that start no whole vector are 0."""
        import jax
        import jax.numpy as jnp

        n_frames = x.shape[-2]
        pad = [(0, 0)] * (x.ndim - 2) + [(0, n_stack - 1), (0, 0)]
        xp = jnp.pad(x, pad)
        v = jnp.concatenate([xp[..., i:i + n_frames, :]
                             for i in range(n_stack)], axis=-1)
        h = v
        for i, (w, b) in enumerate(params):
            h = h @ w + b
            if i < len(params) - 1:
                h = jax.nn.relu(h)
        err = jnp.mean((v - h) ** 2, axis=-1, keepdims=True)
        whole = jnp.arange(n_frames)[:, None] < n_frames - (n_stack - 1)
        return jnp.where(whole, err, 0.0)
    return score


def build_graph(cfg):
    from repro.signal import SignalGraph

    g = SignalGraph(cfg["name"])
    g.stft("spec", "input", frame=cfg["n_fft"], hop=cfg["hop_length"])
    g.magnitude("mag", "spec", onesided=True)
    g.mul("power", "mag", "mag")
    g.mel_filterbank("mel", "power", sr=cfg["sample_rate"],
                     n_mels=cfg["n_mels"])
    g.dnn("logmel", "mel", fn=_logmel)
    g.dnn("score", "logmel", fn=_make_score(cfg["frames"]))
    g.outputs("score", "logmel")
    return g


def make_params(cfg, seed):
    """Seeded weights in the graph's params layout, made on the device
    in one jitted call: Glorot-uniform kernels and N(0, 0.01) biases with
    BatchNorm folded in, as at inference, its statistics taken from a
    calibration clip of the seed's.  Every hidden pre-activation is
    standardised (gamma 1, beta 0, as the source's BatchNormalization
    starts), and the output layer is scaled and shifted to each
    dimension's mean and spread of the calibration vectors, as a trained
    decoder's output would be: the reconstruction is of the input's size,
    so the score depends on every layer.  With the benchmark's own mel
    matrix."""
    import jax
    import jax.numpy as jnp

    dims = cfg["ae_dims"]
    mel = arith.mel_matrix(cfg["n_fft"] // 2 + 1, cfg["sample_rate"],
                           cfg["n_mels"])
    clip = make_audio(cfg, arith.np_rng(seed, CALIBRATION_STREAM),
                      cfg["clip_samples"])
    _, cal = features(arith.Arith(), cfg, mel, clip)
    hi = jax.lax.Precision.HIGHEST

    @jax.jit
    def make(key, cal):
        layers = []
        h = cal
        keys = jax.random.split(key, len(dims) - 1)
        for i, (k, di, do) in enumerate(zip(keys, dims[:-1], dims[1:])):
            kw, kb = jax.random.split(k)
            lim = np.sqrt(6.0 / (di + do))
            w = jax.random.uniform(kw, (di, do), jnp.float32, -lim, lim)
            b = 0.01 * jax.random.normal(kb, (do,), jnp.float32)
            z = jnp.dot(h, w, precision=hi) + b
            mu = jnp.mean(z, axis=0)
            scale = jax.lax.rsqrt(jnp.var(z, axis=0) + BN_EPS)
            shift = 0.0
            if i == len(dims) - 2:
                scale = scale * jnp.std(cal, axis=0)
                shift = jnp.mean(cal, axis=0)
            w, b = w * scale, (b - mu) * scale + shift
            layers.append((w, b))
            h = jnp.dot(h, w, precision=hi) + b
            if i < len(dims) - 2:
                h = jax.nn.relu(h)
        return {"mel": {"weights": jnp.asarray(mel)}, "score": layers}

    return make(arith.prng_key(seed), jnp.asarray(cal, jnp.float32))


def make_audio(cfg, rng, n_samples):
    """Machine-hum stand-in in one-second segments: a 50-300 Hz
    fundamental with 8 harmonics of amplitude 1/k plus N(0, 0.3) noise,
    scaled by 0.1."""
    sr = cfg["sample_rate"]
    n_seg = -(-n_samples // sr)
    t = np.arange(sr) / sr
    f0 = rng.uniform(50.0, 300.0, size=(n_seg, 1))
    sig = np.zeros((n_seg, sr), np.float32)
    for k in range(1, 9):
        ph = rng.uniform(0, 2 * np.pi, size=(n_seg, 1))
        sig += (np.sin(2 * np.pi * k * f0 * t[None] + ph) / k).astype(
            np.float32)
    sig += rng.normal(0.0, 0.3, size=(n_seg, sr)).astype(np.float32)
    return (0.1 * sig).reshape(-1)[:n_samples]


# -- the plain reference ----------------------------------------------------

def features(a, cfg, mel, x):
    """``(logmel, vectors)`` of one clip ``x`` (1-D) at the precision
    ``a`` (``arith.Arith``): the (frames, n_mels) log-mel spectrogram and
    its (frames - 4, 640) stacked vectors."""
    n_fft, hop, n_stack = cfg["n_fft"], cfg["hop_length"], cfg["frames"]
    x = a.r(np.asarray(x, np.float64))
    n_frames = 1 + (x.shape[-1] - n_fft) // hop
    idx = np.arange(n_frames)[:, None] * hop + np.arange(n_fft)[None, :]
    fr = a.r(x[idx] * a.r(arith.hann(n_fft)))
    spec = a.fft(fr)[:, :n_fft // 2 + 1]
    mag = a.r(np.abs(spec))
    power = a.r(mag * mag)
    m = a.mm(power, np.asarray(mel, np.float64).T)
    logmel = a.r(10.0 * np.log10(m + LOG_FLOOR))
    n_vec = n_frames - (n_stack - 1)
    v = np.concatenate([logmel[i:i + n_vec] for i in range(n_stack)],
                       axis=-1)
    return logmel, v


def reference(cfg, params, x, control=False):
    """Outputs of one clip ``x`` (1-D): ``{"score": (frames, 1),
    "logmel": (frames, n_mels)}``.  float64 numpy; ``control`` computes
    one precision lower (``arith.Arith``: ``True`` every stage,
    ``"model"`` the autoencoder alone)."""
    a = arith.Arith(control)
    logmel, v = features(a, cfg, params["mel"]["weights"], x)
    h = v
    layers = params["score"]
    for i, (w, b) in enumerate(layers):
        h = a.r(a.mm_model(h, np.asarray(w, np.float64))
                + a.r(np.asarray(b, np.float64)))
        if i < len(layers) - 1:
            h = np.maximum(h, 0.0)
    err = a.r(np.mean(a.r((v - h) ** 2), axis=-1))
    score = np.zeros((logmel.shape[0], 1))
    score[:len(err), 0] = err
    return {"score": score, "logmel": logmel}


# -- faults planted in the program's weights --------------------------------

def _zero_output(params, seed):
    """The autoencoder's output left at zero."""
    layers = list(params["score"])
    w, b = layers[-1]
    layers[-1] = (0.0 * w, 0.0 * b)
    return dict(params, score=layers)


def _perturb_hidden(params, seed):
    """The fourth hidden layer's kernel perturbed by noise of a tenth of
    its spread."""
    import jax
    layers = list(params["score"])
    w, b = layers[3]
    noise = jax.random.normal(arith.prng_key(seed), w.shape, w.dtype)
    layers[3] = (w + 0.1 * w.std() * noise, b)
    return dict(params, score=layers)


FAULTS = {"ae_output_zeroed": _zero_output,
          "ae_hidden_perturbed": _perturb_hidden}


# -- the work of one request ------------------------------------------------

def flops(cfg, n):
    """Algorithmic floating-point operations of one clip of ``n``
    samples, however the stages are lowered: the window product,
    5 N log2 N per complex N-point FFT, power, mel multiply-adds, the
    log, the autoencoder's multiply-adds and biases over every whole
    vector, and the squared error."""
    n_fft, hop = cfg["n_fft"], cfg["hop_length"]
    n_frames = 1 + (n - n_fft) // hop
    bins = n_fft // 2 + 1
    dims = cfg["ae_dims"]
    per_frame = (n_fft                                # window
                 + 5 * n_fft * int(np.log2(n_fft))    # FFT
                 + bins                               # power
                 + 2 * bins * cfg["n_mels"]           # mel
                 + cfg["n_mels"])                     # log
    per_vec = (sum(2 * di * do + do for di, do in zip(dims[:-1], dims[1:]))
               + 3 * dims[0])                         # squared error, mean
    return n_frames * per_frame + (n_frames - cfg["frames"] + 1) * per_vec
