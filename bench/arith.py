"""Plain arithmetic shared by the configurations' references: the
precision policy (float64, or bfloat16 for the control), DFTs, windows,
mel triangles and the seed-to-key rule.  Imports nothing of the program.
"""

from __future__ import annotations

import numpy as np


def prng_key(seed):
    """A JAX key from any whole seed up to 64 bits: low and high words."""
    import jax
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def np_rng(seed, *stream):
    """A numpy generator for one named stream of a seed."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), *stream]))


def _bf16(x):
    import ml_dtypes
    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float32)


def _fp8(x):
    import ml_dtypes
    return np.asarray(x, np.float32).astype(ml_dtypes.float8_e4m3fn).astype(
        np.float32)


class Arith:
    """The reference's precision.  ``control=False``: float64 throughout.
    ``control=True``: one step below what each configuration states —
    the float32 signal stages in bfloat16 (every value rounded to
    bfloat16, matrix products over bfloat16 operands accumulated in
    float32, DFTs as such products), and the model's products, which the
    configurations state in bfloat16 (XLA's one-pass default on the
    TPU), over float8 (e4m3) operands accumulated in float32.
    ``control="model"``: the model's products alone lowered so, the
    signal stages in float64."""

    def __init__(self, control=False):
        if control not in (False, True, "model"):
            raise ValueError(f"unknown control {control!r}")
        self.control = control is True
        self.model = bool(control)

    def r(self, x):
        """Round a value to the working precision."""
        x = np.asarray(x)
        if not self.control:
            return x.astype(np.complex128 if np.iscomplexobj(x)
                            else np.float64)
        if np.iscomplexobj(x):
            return _bf16(x.real) + 1j * _bf16(x.imag)
        return _bf16(x)

    def mm(self, a, b):
        """Real matrix product ``a @ b`` at the working precision."""
        if not self.control:
            return np.asarray(a, np.float64) @ np.asarray(b, np.float64)
        return _bf16(np.matmul(self.r(a), self.r(b), dtype=np.float32))

    def mm_model(self, a, b):
        """Matrix product of the model (mask CNN, autoencoder)."""
        if not self.model:
            return self.mm(a, b)
        return _bf16(np.matmul(_fp8(a), _fp8(b), dtype=np.float32))

    def _cmm(self, a, w):
        ar, ai = np.real(a), np.imag(a)
        wr, wi = np.real(w), np.imag(w)
        re = self.mm(ar, wr) - self.mm(ai, wi)
        im = self.mm(ar, wi) + self.mm(ai, wr)
        return self.r(re + 1j * im)

    def fft(self, x):
        """DFT along the last axis."""
        if not self.control:
            return np.fft.fft(x, axis=-1)
        return self._cmm(x, dft_matrix(x.shape[-1]))

    def ifft(self, x):
        """Inverse DFT along the last axis."""
        if not self.control:
            return np.fft.ifft(x, axis=-1)
        n = x.shape[-1]
        return self.r(self._cmm(x, np.conj(dft_matrix(n))) / n)


def dft_matrix(n):
    k = np.arange(n)
    return np.exp(-2j * np.pi * np.outer(k, k) / n)


def hann(n):
    """Periodic Hann window."""
    return 0.5 * (1.0 - np.cos(2.0 * np.pi * np.arange(n) / n))


def gelu_tanh(x):
    return 0.5 * x * (1.0 + np.tanh(np.sqrt(2.0 / np.pi)
                                    * (x + 0.044715 * x ** 3)))


def mel_matrix(bins, sr, n_mels):
    """(n_mels, bins) HTK-mel triangles over a one-sided spectrum of
    ``bins`` linear frequencies in [0, sr/2], float32."""
    def hz2mel(f):
        return 2595.0 * np.log10(1.0 + np.asarray(f) / 700.0)

    def mel2hz(m):
        return 700.0 * (10.0 ** (np.asarray(m) / 2595.0) - 1.0)

    freqs = np.linspace(0.0, sr / 2.0, bins)
    edges = mel2hz(np.linspace(0.0, hz2mel(sr / 2.0), n_mels + 2))
    lo, mid, hi = edges[:-2, None], edges[1:-1, None], edges[2:, None]
    up = (freqs[None] - lo) / (mid - lo)
    down = (hi - freqs[None]) / (hi - mid)
    return np.clip(np.minimum(up, down), 0.0, None).astype(np.float32)
