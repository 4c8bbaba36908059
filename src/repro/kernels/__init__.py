"""Pallas TPU kernels for SigDLA's compute hot-spots.

Each kernel is the fused "fabric + computing array" step of the paper,
re-tiled for the TPU memory hierarchy (HBM -> VMEM -> MXU):

- bitserial_mm : variable-bitwidth integer GEMM via 4-bit plane
                 decomposition + shift-add (paper §IV / Fig 2).
- shuffle_gemm : programmable gather/pad (an XLA gather, or a layout
                 copy where the plan is a strided permutation) feeding
                 a GEMM kernel (paper §V: the shuffling fabric feeding
                 the array).
- fft_stage    : one radix-2 butterfly stage = composed shuffle plan +
                 per-twiddle-class 4x4 matmuls (paper Fig 3a).
- fir_conv     : multi-phase FIR (im2col window gather + tap-bank GEMM,
                 structural zeros = DPU pads; paper Fig 3b + our phased
                 mapping).

Kernels target TPU (BlockSpec/VMEM tiling, MXU-aligned tiles) and are
validated on CPU with ``interpret=True`` against the pure-jnp oracles in
each ``ref.py``.
"""

from .bitserial_mm.ops import bitserial_matmul
from .shuffle_gemm.ops import shuffle_gemm, shuffle_gemm_grouped
from .fft_stage.ops import fft_stage
from .fir_conv.ops import fir_conv
from .flash_attention.ops import flash_attention

__all__ = ["bitserial_matmul", "shuffle_gemm", "shuffle_gemm_grouped",
           "fft_stage", "fir_conv", "flash_attention",
           "interpret_default", "compiled_supported"]


def interpret_default() -> bool:
    """The Pallas ``interpret=`` default for every kernel wrapper in this
    package (they resolve ``interpret=None`` through here): interpret
    mode on CPU (CI / this container), compiled on real devices.

    Override with the ``REPRO_PALLAS_INTERPRET`` environment variable
    (``1``/``true`` forces interpret everywhere, ``0``/``false`` forces
    compiled kernels) — e.g. to smoke-test the compiled path in
    interpret-capable environments or to debug on device."""
    import os
    env = os.environ.get("REPRO_PALLAS_INTERPRET", "").strip().lower()
    if env in ("1", "true", "yes", "on"):
        return True
    if env in ("0", "false", "no", "off"):
        return False
    import jax
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret):
    """Shared ``interpret=`` resolution for every kernel wrapper:
    ``None`` defers to :func:`interpret_default` (per call — never baked
    into a jit trace), anything else is coerced to bool."""
    return interpret_default() if interpret is None else bool(interpret)


def default_interpret() -> bool:
    """Deprecated alias of :func:`interpret_default`."""
    return interpret_default()


_COMPILED_SUPPORTED = None


def compiled_supported() -> bool:
    """True when this host's jax can lower Pallas kernels with
    ``interpret=False`` (TPU / supported GPU; the CPU backend is
    interpret-only in current jax releases).

    Probed once with a trivial kernel and cached for the process.  The
    ``--compiled`` bench sweeps and the ``compiled-kernels`` CI lane use
    this to *record* "compiled unsupported" / skip-with-reason instead
    of failing — green-but-honest — when ``REPRO_PALLAS_INTERPRET=0``
    forces the compiled path on a host that cannot run it."""
    global _COMPILED_SUPPORTED
    if _COMPILED_SUPPORTED is None:
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        def _copy(x_ref, o_ref):
            o_ref[...] = x_ref[...]

        try:
            out = pl.pallas_call(
                _copy,
                out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
                interpret=False)(jnp.zeros((8, 128), jnp.float32))
            out.block_until_ready()
            _COMPILED_SUPPORTED = True
        except Exception:
            _COMPILED_SUPPORTED = False
    return _COMPILED_SUPPORTED
