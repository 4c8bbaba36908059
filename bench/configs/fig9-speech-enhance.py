"""The SigDLA paper's Fig. 9 speech-enhancement pipeline (arXiv:2407.12565):
learned FIR front-end -> Hann STFT -> mask CNN -> masked spectrum ->
iSTFT, with a mel monitoring tap on the masked spectrum.

The graph and the CNN are copied from ``examples/speech_enhancement.py``
(``build_graph``, ``init_cnn``, ``cnn_mask``) and the audio generator from
``repro.data.SignalStream``, so that edits there cannot move this
yardstick.  ``reference`` is a plain numpy implementation of the same
semantics that imports nothing from the program.
"""

from __future__ import annotations

import numpy as np

from bench import arith

STREAM_AXES = {"out": -1, "mel_tap": 0}


# -- the program side: graph, weights, audio --------------------------------

def cnn_mask(params, spec):
    """Complex spectrum (B, T, F) -> sigmoid mask (B, T, F)
    (examples/speech_enhancement.py ``cnn_mask``)."""
    import jax
    import jax.numpy as jnp

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


def build_graph(cfg):
    """The Fig-9 SigProgram (examples/speech_enhancement.py
    ``build_graph``), with the iSTFT left at its natural length."""
    from repro.signal import SignalGraph

    ch = cfg["cnn_channels"]
    g = SignalGraph(cfg["name"])
    taps0 = np.zeros(cfg["fir_taps"], np.float32)
    taps0[0] = 1.0
    g.fir("front", "input", taps=taps0)
    g.stft("spec", "front", frame=cfg["frame"], hop=cfg["hop"])
    g.dnn("mask", "spec", fn=cnn_mask, frame_context=len(ch) - 1)
    g.mul("enh", "spec", "mask")
    g.istft("out", "enh", hop=cfg["hop"], length=None)
    g.magnitude("mag", "enh", onesided=True)
    g.mel_filterbank("mel_tap", "mag", sr=cfg["sample_rate"],
                     n_mels=cfg["n_mels"])
    g.outputs("out", "mel_tap")
    return g


def make_params(cfg, seed):
    """Seeded weights in the graph's params layout, made on the device
    in one jitted call: FIR taps (a delta plus noise), the CNN kernels
    (``init_cnn``'s scaling) and the benchmark's own mel matrix."""
    import jax
    import jax.numpy as jnp

    ch = cfg["cnn_channels"]
    k = cfg["cnn_kernel"]
    mel = arith.mel_matrix(cfg["frame"] // 2 + 1, cfg["sample_rate"],
                           cfg["n_mels"])

    @jax.jit
    def make(key):
        kf, kc = jax.random.split(key)
        taps = jnp.zeros(cfg["fir_taps"]).at[0].set(1.0) \
            + 0.05 * jax.random.normal(kf, (cfg["fir_taps"],))
        ks = jax.random.split(kc, len(ch) - 1)
        cnn = [jax.random.normal(kk, (k, k, ci, co)) / np.sqrt(k * k * ci)
               for kk, ci, co in zip(ks, ch[:-1], ch[1:])]
        return {"front": {"taps": taps.astype(jnp.float32)},
                "mask": [w.astype(jnp.float32) for w in cnn],
                "mel_tap": {"weights": jnp.asarray(mel)}}

    return make(arith.prng_key(seed))


def make_audio(cfg, rng, n_samples):
    """Noisy multi-sine speech stand-in, one-second segments
    (repro.data.SignalStream.batch_at): four sines of 80-3500 Hz with
    amplitudes 0.2-1 plus N(0, 0.8) noise."""
    sr = cfg["sample_rate"]
    n_seg = -(-n_samples // sr)
    t = np.arange(sr) / sr
    clean = np.zeros((n_seg, sr), np.float32)
    for _ in range(4):
        f = rng.uniform(80.0, 3500.0, size=(n_seg, 1))
        a = rng.uniform(0.2, 1.0, size=(n_seg, 1))
        ph = rng.uniform(0, 2 * np.pi, size=(n_seg, 1))
        clean += (a * np.sin(2 * np.pi * f * t[None] + ph)).astype(np.float32)
    noise = rng.normal(0.0, 0.8, size=(n_seg, sr)).astype(np.float32)
    return (clean + noise).reshape(-1)[:n_samples]


# -- the plain reference ----------------------------------------------------

def _conv3x3(a, x, w):
    """SAME cross-correlation, NHWC without N: x (T, F, cin), w (k, k,
    cin, cout)."""
    k = w.shape[0]
    h = k // 2
    xp = np.pad(x, ((h, h), (h, h), (0, 0)))
    t, f = x.shape[:2]
    out = 0.0
    for dy in range(k):
        for dx in range(k):
            out = out + a.mm_model(xp[dy:dy + t, dx:dx + f], w[dy, dx])
    return a.r(out)


def reference(cfg, params, x, control=False):
    """Outputs of one request ``x`` (1-D): ``{"out": (n,), "mel_tap":
    (frames, n_mels)}``.  float64 numpy; ``control=True`` computes one
    precision lower (``arith.Arith``)."""
    a = arith.Arith(control)
    frame, hop = cfg["frame"], cfg["hop"]
    taps = np.asarray(params["front"]["taps"], np.float64)
    x = a.r(np.asarray(x, np.float64))
    n = x.shape[-1]
    # causal FIR: y[i] = sum_t h[t] x[i - t]
    cols = np.stack([np.concatenate([np.zeros(t), x[:n - t]])
                     for t in range(len(taps))], axis=-1)
    y = a.mm(cols, taps)
    n_frames = 1 + (n - frame) // hop
    idx = np.arange(n_frames)[:, None] * hop + np.arange(frame)[None, :]
    fr = a.r(y[idx] * a.r(arith.hann(frame)))
    spec = a.fft(fr)
    mag = np.abs(spec)
    feats = np.stack([a.r(np.log1p(mag)),
                      a.r(np.cos(np.angle(spec)))], axis=-1)
    h = feats
    ws = params["mask"]
    for i, w in enumerate(ws):
        h = _conv3x3(a, h, np.asarray(w, np.float64))
        if i < len(ws) - 1:
            h = a.r(arith.gelu_tanh(h))
    m = a.r(1.0 / (1.0 + np.exp(-h[..., 0])))
    enh = a.r(spec * m)
    frames_t = np.real(a.ifft(enh))
    out = np.zeros((n_frames - 1) * hop + frame)
    for f in range(n_frames):
        out[f * hop:f * hop + frame] += frames_t[f]
    out = a.r(out)
    mel_w = np.asarray(params["mel_tap"]["weights"], np.float64)
    mag2 = a.r(np.abs(enh[:, :frame // 2 + 1]))
    mel = a.mm(mag2, mel_w.T)
    return {"out": out, "mel_tap": mel}


# -- the work of one request ------------------------------------------------

def flops(cfg, n):
    """Algorithmic floating-point operations of one request of ``n``
    samples, however the stages are lowered: FIR and mel
    multiply-adds, 5 N log2 N per complex N-point FFT (forward and
    inverse per frame), the window and mask products, the CNN's
    multiply-adds, and the overlap-add."""
    frame, hop = cfg["frame"], cfg["hop"]
    n_frames = 1 + (n - frame) // hop
    ch = cfg["cnn_channels"]
    k = cfg["cnn_kernel"]
    fir = 2 * cfg["fir_taps"] * n
    fft = 5 * frame * int(np.log2(frame))
    conv = 2 * k * k * sum(ci * co for ci, co in zip(ch[:-1], ch[1:]))
    bins = frame // 2 + 1
    per_frame = (frame                   # window
                 + 2 * fft               # STFT and iSTFT
                 + conv * frame          # mask CNN over every bin
                 + 2 * frame             # complex spectrum x real mask
                 + frame                 # overlap-add
                 + 2 * bins * cfg["n_mels"])
    return fir + n_frames * per_frame
