"""Shuffling-fabric + GEMM kernels (paper §V).

The ASIC inserts the fabric between SRAM and the MAC array.  Here the
fabric pass — gather, constant padding and the optional per-element
``diag`` scale — is one XLA pass ahead of the kernel, and the Pallas
kernel is the array pass:

    out[b, r, :] = (x[b, idx[r, :]] | pad) (* scale) @ w

``idx`` rows are the compiled ShufflePlan (PAD = -1 entries take
``pad_vals``); ``scale`` is the plan's optional constant per-element
``diag`` (window taper, conjugation signs, 1/n).  Mosaic lowers no
general gather over a VMEM block, so the fabric pass stays in XLA; it
writes the rows straight into the kernel's layout, at no extra pass.

The fabric pass has two lowerings, chosen from the plan alone.  When
the plan, laid out as the kernel's operand, is a strided permutation of
``x`` (:func:`lane_form`, which is :func:`repro.core.fabric.strided_form`
on that layout), the caller passes that static ``form`` and the pass is
a reshape and transpose of ``x``: a layout copy.  Without a form it is
``jnp.take`` + ``where`` over the index (framing, im2col, pad
constants).  The index is traced inside the jitted entry points, so the
form is computed on the host beforehand and passed as a static
argument.

Layout: rows sit on the 128 lanes and the contraction on the sublanes.
The gathered operand is ``(B, G, t, C)`` — group ``g``'s ``C`` rows,
each ``t`` long — and each grid step computes one lane block

    out[b, g, :, c0:c1] = w[g].T @ gathered[b, g, :, c0:c1]

so every block is lane-dense whatever ``t`` and ``n_out`` are (the FFT
butterfly has t = n_out = 4).  A group's ``C = nb * reps`` lanes run
with the repeat (an FFT's frame) minor.

Two entry points share the kernel:

  * :func:`shuffle_gemm_blocks` — one shared ``(t, n_out)`` operand for
    every row (FIR taps, DCT matrix, mel filterbank): ``G = 1``.
  * :func:`shuffle_gemm_grouped_blocks` — a *grouped* operand
    ``(G, t, n_out)``: row ``r`` (flat layout ``(reps, G, nb)``)
    contracts against group ``(r // nb) % G``.  This is the FFT
    butterfly shape — per-twiddle-class (nb, 4) x (4, 4) matmuls — for
    arbitrary gather plans (the graph compiler's fused/folded stages).

``scopes`` (optional): the named scopes of the gather and of the kernel
call with its layout ops — the graph steps they run for, so the
compiled program's op metadata tells the fabric pass from the array
pass.
"""

from __future__ import annotations

import contextlib
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ...core import fabric

LANES = 128
# VMEM bytes for one lane block of the gathered operand (it is
# double-buffered, beside the output block); keeps every block well
# inside the default scoped VMEM limit whatever the contraction length.
BLOCK_BYTES = 2 << 20
MAX_BLOCK_LANES = 2048


def _to_lanes(a, reps, groups, nb):
    """``(R, t)`` rows in ``(reps, G, nb)`` order -> ``(G, t, nb * reps)``:
    the kernel operand's layout, before the lanes are padded.  The
    repeat index (an FFT's frame) is the minor lane digit: each lane is
    its own GEMM column, so the order is free, and with the frames on
    the lanes a strided fabric pass moves whole lane rows."""
    t = a.shape[-1]
    a = a.reshape(reps, groups, nb, t).transpose(1, 3, 2, 0)
    return a.reshape(groups, t, nb * reps)


def lane_form(idx, reps: int, groups: int, nb: int, n_in: int):
    """The static form of the fabric pass that gathers ``idx`` (``(R,
    t)`` blocks) from an ``n_in``-long source into the kernel's layout:
    :func:`repro.core.fabric.strided_form` of the index laid out by
    :func:`_to_lanes` on the host, or ``None`` when the pass must
    gather."""
    lanes = _to_lanes(np.asarray(idx), reps, groups, nb)
    return fabric.strided_form(lanes, n_in)


def _gather(x, idx, pad_vals, scale, form=None):
    """The fabric pass in XLA: ``x`` (B, n_in) gathered through ``idx``
    (any shape, PAD = -1 takes ``pad_vals``), then scaled.  With a
    ``form`` (:func:`lane_form`) the pass is the reshape/transpose it
    describes, zero-padded to ``idx``'s last axis, and ``idx`` and
    ``pad_vals`` are not read.  A form is used only where it spans all
    of ``x``: a plan that reads a prefix of a longer source gathers."""
    if form is not None and math.prod(form[0]) != x.shape[1]:
        form = None
    if form is None:
        g = jnp.take(x, jnp.maximum(idx, 0), axis=1)
        g = jnp.where(idx < 0, pad_vals.astype(g.dtype), g)
    else:
        g = fabric.apply_strided(x, form)
        g = jnp.pad(g, [(0, 0)] * (g.ndim - 1)
                    + [(0, idx.shape[-1] - g.shape[-1])])
    if scale is not None:
        g = g * scale.astype(g.dtype)
    return g


def _scope(name):
    return jax.named_scope(name) if name else contextlib.nullcontext()


def _kernel(g_ref, w_ref, o_ref):
    # f32 operands contract at full f32 precision, as the reference does
    o_ref[0, 0] = jax.lax.dot_general(
        w_ref[0], g_ref[0, 0], (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


def _block_lanes(c: int, t: int, n_out: int, itemsize: int) -> int:
    cap = BLOCK_BYTES // (itemsize * max(t, n_out)) // LANES * LANES
    want = -(-c // LANES) * LANES
    return max(LANES, min(want, cap, MAX_BLOCK_LANES))


def _grouped_gemm(x, idx, pad_vals, w, reps, groups, nb, interpret, scale,
                  scopes=None, form=None):
    """x: (B, n_in); idx/pad_vals[/scale]: (R, t), R = reps*G*nb rows in
    (reps, G, nb) order; w: (G, t, n_out) -> (B, reps, G, nb, n_out).
    ``form``: the fabric pass's :func:`lane_form`, or ``None``."""
    gather_scope, kernel_scope = scopes or (None, None)
    b = x.shape[0]
    r, t = idx.shape
    n_out = w.shape[-1]
    c = reps * nb
    bl = _block_lanes(c, t, n_out, x.dtype.itemsize)
    cp = -(-c // bl) * bl

    def to_lanes(a, fill):                      # (R, t) -> (G, t, Cp)
        return jnp.pad(_to_lanes(a, reps, groups, nb),
                       ((0, 0), (0, 0), (0, cp - c)), constant_values=fill)

    with _scope(gather_scope):
        g = _gather(x, to_lanes(idx, -1), to_lanes(pad_vals, 0),
                    None if scale is None else to_lanes(scale, 0), form)
    with _scope(kernel_scope):
        y = pl.pallas_call(
            _kernel,
            grid=(b, groups, cp // bl),
            in_specs=[pl.BlockSpec((1, 1, t, bl),
                                   lambda i, j, k: (i, j, 0, k)),
                      pl.BlockSpec((1, n_out, t), lambda i, j, k: (j, 0, 0))],
            out_specs=pl.BlockSpec((1, 1, n_out, bl),
                                   lambda i, j, k: (i, j, 0, k)),
            out_shape=jax.ShapeDtypeStruct((b, groups, n_out, cp), x.dtype),
            interpret=interpret,
            name="shuffle_gemm",
        )(g, jnp.swapaxes(w, 1, 2).astype(x.dtype))
        y = y[..., :c].reshape(b, groups, n_out, nb, reps)
        return y.transpose(0, 4, 1, 3, 2)


@functools.partial(jax.jit, static_argnames=("interpret", "scopes",
                                             "form"))
def shuffle_gemm_blocks(x: jax.Array, idx: jax.Array, pad_vals: jax.Array,
                        w: jax.Array, interpret: bool = True,
                        scale: jax.Array | None = None,
                        scopes: tuple | None = None,
                        form: tuple | None = None) -> jax.Array:
    """x: (B, n_in); idx/pad_vals[/scale]: (R, t); w: (t, n_out) ->
    (B, R, n_out).  ``form``: ``lane_form(idx, R, 1, 1, n_in)`` where
    the pass is a strided permutation, else ``None``."""
    r = idx.shape[0]
    y = _grouped_gemm(x, idx, pad_vals, w[None], r, 1, 1, interpret,
                      scale, scopes, form)
    with _scope(scopes and scopes[1]):
        return y.reshape(x.shape[0], r, w.shape[-1])


@functools.partial(jax.jit, static_argnames=("reps", "groups", "nb",
                                             "interpret", "scopes", "form"))
def shuffle_gemm_grouped_blocks(x: jax.Array, idx: jax.Array,
                                pad_vals: jax.Array, w: jax.Array,
                                reps: int, groups: int, nb: int,
                                interpret: bool = True,
                                scale: jax.Array | None = None,
                                scopes: tuple | None = None,
                                form: tuple | None = None
                                ) -> jax.Array:
    """x: (B, n_in); idx/pad_vals[/scale]: (R, t) with R = reps*G*nb in
    (reps, G, nb) row order; w: (G, t, n_out) -> (B, R * n_out) flat in
    the same row order (the einsum's natural ``...fjbo`` layout).
    ``form``: ``lane_form(idx, reps, G, nb, n_in)`` or ``None``."""
    y = _grouped_gemm(x, idx, pad_vals, w, reps, groups, nb, interpret,
                      scale, scopes, form)
    with _scope(scopes and scopes[1]):
        return y.reshape(x.shape[0], -1)
