"""The configurations' algorithmic operation counts against hand counts
at small sizes."""

from bench import harness


def test_fig9_flops_hand_count():
    cfg, mod = harness.load_config("fig9-speech-enhance")
    cfg = dict(cfg, frame=8, hop=4, fir_taps=3, cnn_channels=[2, 3, 1],
               cnn_kernel=3, n_mels=2)
    n = 16                                   # 1 + (16 - 8) // 4 = 3 frames
    fir = 2 * 3 * 16
    fft = 5 * 8 * 3                          # 5 N log2 N, N = 8
    conv = 2 * 9 * (2 * 3 + 3 * 1)           # per bin
    per_frame = 8 + 2 * fft + conv * 8 + 2 * 8 + 8 + 2 * 5 * 2
    assert mod.flops(cfg, n) == fir + 3 * per_frame


def test_dcase_flops_hand_count():
    cfg, mod = harness.load_config("dcase2020-t2-ae")
    cfg = dict(cfg, n_fft=8, hop_length=4, n_mels=2, frames=2,
               ae_dims=[4, 3, 4])
    n = 20                                   # 1 + (20 - 8) // 4 = 4 frames
    per_frame = 8 + 5 * 8 * 3 + 5 + 2 * 5 * 2 + 2
    per_vec = (2 * 4 * 3 + 3) + (2 * 3 * 4 + 4) + 3 * 4
    assert mod.flops(cfg, n) == 4 * per_frame + 3 * per_vec


def test_flops_at_published_sizes():
    cfg, mod = harness.load_config("dcase2020-t2-ae")
    # 311 frames of a 10 s clip; the autoencoder dominates
    assert 2.0e8 < mod.flops(cfg, 160_000) < 2.5e8
    cfg, mod = harness.load_config("fig9-speech-enhance")
    assert 2.0e8 < mod.flops(cfg, 32_000) < 2.3e8
