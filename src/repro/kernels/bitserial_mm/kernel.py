"""Variable-bitwidth integer GEMM kernel (SigDLA computing array, §IV).

Operands arrive pre-decomposed into 4-bit digit planes (int8 carriers):
``a_planes`` (pa, M, K), ``w_planes`` (pw, K, N).  The kernel accumulates

    out = sum_{i<pa, j<pw} (a_i @ w_j) << 4*(i+j)        (int32)

which is bit-exact with the direct product of the original aw/ww-bit
integers — the same recursive shift-add recombination as the paper's
precision-scalable PE (shifts 0/4/4/8 for 8x8, max 24 for 16x16).

TPU mapping: each plane-pair matmul is an int8 MXU pass; the plane loops
are unrolled in the kernel so XLA pipelines them over the same VMEM-resident
blocks.  Grid = (M/bm, N/bn, K/bk), K innermost for accumulation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(a_ref, w_ref, o_ref, *, pa: int, pw: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    acc = jnp.zeros(o_ref.shape, jnp.int32)
    for i in range(pa):
        for j in range(pw):
            # int8 planes straight into the MXU, accumulated in int32
            part = jax.lax.dot_general(
                a_ref[i], w_ref[j], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)
            acc = acc + (part << (4 * (i + j)))
    o_ref[...] += acc


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def bitserial_matmul_planes(a_planes: jax.Array, w_planes: jax.Array,
                            bm: int = 128, bn: int = 128, bk: int = 128,
                            interpret: bool = True) -> jax.Array:
    """(pa, M, K) x (pw, K, N) int8 planes -> (M, N) int32.  M, K, N must be
    multiples of the block sizes (ops.py pads)."""
    pa, m, k = a_planes.shape
    pw, k2, n = w_planes.shape
    assert k == k2, (k, k2)
    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        functools.partial(_kernel, pa=pa, pw=pw),
        grid=grid,
        in_specs=[
            pl.BlockSpec((pa, bm, bk), lambda i, j, kk: (0, i, kk)),
            pl.BlockSpec((pw, bk, bn), lambda i, j, kk: (0, kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        interpret=interpret,
        name="bitserial_mm",
    )(a_planes, w_planes)
