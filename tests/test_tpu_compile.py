"""Ahead-of-time compiles of the ``pallas`` backend's kernels for a TPU
v5e chip that is described, not attached.

The TPU compiler refuses what interpret mode accepts (block shapes off
the (8, 128) tiling, in-kernel gathers Mosaic cannot lower, int32 MXU
operands, VMEM overruns), so each kernel is compiled with
``interpret=False`` at the shapes the Fig-9 speech-enhancement program
hands it at its 65,536-sample serving bucket with 8 rows.  Nothing runs.

The topology is described inside a fixture, never at import: only one
process may load the TPU compiler library at a time, and pytest-xdist
workers all import this module.
"""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

B = 8                       # SignalService(batch_size=8) rows per bucket
T = 65_536                  # the 65,536-sample (about 4 s at 16 kHz) bucket
FRAMES = 511                # 1 + (T - 256) // 128 STFT frames
FFT_ROWS = FRAMES * 128     # butterfly rows: 128 per 256-point frame


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        # what is compiled for a described chip cannot be read back from
        # the persistent cache: keep it out of the cache entirely
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel_compiles(fn, args, kwargs):
    compiled = fn.lower(*args, **kwargs).compile()
    assert "tpu_custom_call" in compiled.as_text()


# (name, n_in, rows, t, n_out): the shared-operand GEMMs of Fig 9
GEMM_CASES = [
    ("fir_front", T, T, 9, 1),                  # 9-tap learned FIR
    ("mel_tap", FRAMES * 129, FRAMES, 129, 24),  # 129 bins -> 24 mels
]


@pytest.mark.parametrize("case", GEMM_CASES, ids=lambda c: c[0])
def test_shuffle_gemm_blocks_compiles_for_v5e(one_chip, case):
    from repro.kernels.shuffle_gemm.kernel import shuffle_gemm_blocks
    _, n_in, rows, t, n_out = case
    args = (_spec((B, n_in), jnp.float32, one_chip),
            _spec((rows, t), jnp.int32, one_chip),
            _spec((rows, t), jnp.float32, one_chip),
            _spec((t, n_out), jnp.float32, one_chip))
    _assert_kernel_compiles(shuffle_gemm_blocks, args,
                            {"interpret": False})


# (n_in, groups, scaled): the radix-2 butterfly stages of the STFT;
# the first stage gathers the framed signal and carries the window
FFT_CASES = [(T, 1, True), (4 * FFT_ROWS, 8, False),
             (4 * FFT_ROWS, 128, False)]


@pytest.mark.parametrize("case", FFT_CASES,
                         ids=lambda c: f"groups{c[1]}")
def test_shuffle_gemm_grouped_blocks_compiles_for_v5e(one_chip, case):
    from repro.kernels.shuffle_gemm.kernel import \
        shuffle_gemm_grouped_blocks
    n_in, groups, scaled = case
    nb = 128 // groups
    args = (_spec((B, n_in), jnp.float32, one_chip),
            _spec((FFT_ROWS, 4), jnp.int32, one_chip),
            _spec((FFT_ROWS, 4), jnp.float32, one_chip),
            _spec((groups, 4, 4), jnp.float32, one_chip))
    kwargs = dict(reps=FRAMES, groups=groups, nb=nb, interpret=False)
    if scaled:
        kwargs["scale"] = _spec((FFT_ROWS, 4), jnp.float32, one_chip)
    _assert_kernel_compiles(shuffle_gemm_grouped_blocks, args, kwargs)


# (name, planes_a, planes_w, m, k, n, bm, bk, bn): the int-routed FIR
# and mel GEMMs as bitserial_matmul pads them (8-bit = 2 digit planes)
BITSERIAL_CASES = [
    ("fir_8x8", 2, 2, B * T, 9, 8, 128, 9, 8),
    ("mel_8x8", 2, 2, 4096, 256, 24, 128, 128, 24),
    ("mel_16x8", 4, 2, 4096, 256, 24, 128, 128, 24),
]


@pytest.mark.parametrize("case", BITSERIAL_CASES, ids=lambda c: c[0])
def test_bitserial_matmul_planes_compiles_for_v5e(one_chip, case):
    from repro.kernels.bitserial_mm.kernel import bitserial_matmul_planes
    _, pa, pw, m, k, n, bm, bk, bn = case
    args = (_spec((pa, m, k), jnp.int8, one_chip),
            _spec((pw, k, n), jnp.int8, one_chip))
    _assert_kernel_compiles(bitserial_matmul_planes, args,
                            dict(bm=bm, bk=bk, bn=bn, interpret=False))


def test_pallas_training_gradient_compiles_for_v5e(one_chip):
    """The backward pass runs the same kernels at other shapes (the
    adjoint gathers, the transposed operands): the Fig-9 program's loss
    gradient on the ``pallas`` backend compiles whole for the chip."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from chip_smoke import build_program
    from repro.signal.backends import PallasBackend

    graph, params = build_program(T)
    compiled = graph.compile(T, backend=PallasBackend(interpret=False))

    def loss(outs, clean):
        return jnp.mean((outs["out"] - clean) ** 2)

    specs = jax.tree_util.tree_map(
        lambda a: _spec(np.shape(a), jnp.asarray(a).dtype, one_chip), params)
    x = _spec((B, T), jnp.float32, one_chip)
    _assert_kernel_compiles(jax.jit(compiled.value_and_grad(loss)),
                            (specs, x, x), {})


def test_bitserial_pads_match_compiled_cases():
    """The block sizes above are the ones bitserial_matmul picks for the
    Fig-9 GEMMs, so the compiles cover what the int route runs."""
    from repro.kernels.bitserial_mm import ops
    seen = []
    real = ops.bitserial_matmul_planes

    def record(ap, wp, bm, bn, bk, interpret):
        seen.append((ap.shape[0], wp.shape[0], *ap.shape[1:], wp.shape[2],
                     bm, bk, bn))
        return real(ap, wp, bm=bm, bn=bn, bk=bk, interpret=interpret)

    a = np.ones((B, FRAMES, 129), np.int32)
    w = np.ones((129, 24), np.int32)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "bitserial_matmul_planes", record)
        # the unjitted body, so no cached trace skips the recorder
        jax.eval_shape(lambda a, w: ops._bitserial_matmul.__wrapped__(
            a, w, 8, 8, 128, 128, 128, True), a, w)
    assert seen == [case[1:] for case in BITSERIAL_CASES
                    if case[0] == "mel_8x8"]
