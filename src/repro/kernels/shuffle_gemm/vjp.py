"""Reverse-mode rules for the fused shuffle-GEMM kernels.

The forward op is one gather∘einsum group: ``out = reshape(gather(x)
(* diag), (rows, t)) @ w``.  Its transpose is *another* gather∘einsum
group — the fabric is its own adjoint — so the whole backward pass runs
through the same fabric+kernel machinery instead of falling back to an
XLA scatter:

  * ``d_gathered = d_out @ w.T`` — the transposed GEMM, fed by the
    *identity* gather (each output row streams its own cotangent row);
  * ``d_x`` — scatter-as-gather of the inverse index map
    (:func:`repro.core.fabric.adjoint_plan`): gather the (up to ``m``)
    forward positions reading each source element, scale by the forward
    ``diag`` en route, and reduce the ``m`` slots on the array against a
    ones vector — a width-``m`` GEMM;
  * ``d_w = einsum('brt,bro->to', gather(x) * diag, d_out)`` — the
    gathered activations against the cotangent, a dense GEMM XLA already
    fuses optimally.

The adjoint lowering (inverse plan blocks + reduction operand) is built
from the ``run_steps_reference``-shaped program of
:func:`repro.core.exec_ir.adjoint_gather_steps` and cached through the
backend-keyed plan cache under the ``"pallas:vjp"`` label, independent
of the forward ``"pallas"`` lowerings.

Statics (plan / diag / rows / interpret) are closed over per call rather
than passed through ``nondiff_argnums`` — ``ShufflePlan`` holds numpy
arrays and is not hashable; the closures cost nothing since every plan
artifact is already built and cached at graph-compile time.
"""

from __future__ import annotations

import hashlib
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ...core.fabric import ShufflePlan, apply_plan
from .kernel import (lane_form, shuffle_gemm_blocks,
                     shuffle_gemm_grouped_blocks)

# plan-cache label for adjoint (VJP) lowerings — deliberately distinct
# from the forward backend name so plan_cache_info()["by_backend"]
# accounts forward and backward lowerings independently.
VJP_CACHE_BACKEND = "pallas:vjp"


def plan_blocks(plan: ShufflePlan, diag, rows: int, dtype):
    """Reshape a flat plan (+ optional diag scale) into the kernels'
    (rows, t) row-major blocks."""
    t = plan.n_out // rows
    idx = np.asarray(plan.gather_idx, np.int32).reshape(rows, t)
    pads = np.asarray(plan.pad_values).reshape(rows, t)
    scale = None if diag is None else \
        np.asarray(diag, dtype).reshape(rows, t)
    return t, idx, pads, scale


def blocks_call(xb: jax.Array, idx, pads, w: jax.Array,
                interpret: bool, scale=None, scopes=None,
                form=None) -> jax.Array:
    """Run the shared-operand kernel on host-built blocks.
    ``xb``: (B, n_in) -> (B, rows, n_out).  ``form``: the blocks'
    :func:`~.kernel.lane_form`, or ``None`` to gather."""
    return shuffle_gemm_blocks(
        xb, jnp.asarray(idx), jnp.asarray(pads, dtype=xb.dtype), w,
        interpret=interpret,
        scale=None if scale is None else jnp.asarray(scale), scopes=scopes,
        form=form)


def _identity_blocks(rows: int, t: int, reps: int, groups: int = 1,
                     nb: int = 1):
    """Blocks of the identity gather over a flat (rows * t) stream —
    feeds each kernel row its own slice, used to route the cotangent
    into the transposed GEMM — and their lane form (a transpose)."""
    idx = np.arange(rows * t, dtype=np.int32).reshape(rows, t)
    return (idx, np.zeros((rows, t), np.float32),
            lane_form(idx, reps, groups, nb, rows * t))


def _digest(plan: ShufflePlan, diag, n_in: int) -> tuple:
    h = hashlib.sha1()
    for arr in (plan.gather_idx, plan.pad_values,
                np.zeros(0) if diag is None else np.asarray(diag)):
        a = np.ascontiguousarray(arr)
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return (h.hexdigest(), n_in)


def adjoint_lowering(plan: ShufflePlan, n_in: int, diag=None):
    """Kernel-ready blocks of the adjoint program for one forward
    gather: ``(idx, pads, scale, ones, form)`` such that gathering the
    flat cotangent through ``(idx, pads, scale)`` — as a transpose where
    ``form`` is not ``None``: the adjoint of a strided permutation is
    one — and contracting each row against ``ones`` (an ``(m, 1)``
    operand) yields ``d_x`` —
    the two steps of :func:`repro.core.exec_ir.adjoint_gather_steps`
    lowered the same way the backend lowers any forward group.

    Cached through the backend-keyed plan cache under
    ``VJP_CACHE_BACKEND`` so repeated ``value_and_grad`` calls rebuild
    nothing; falls back to a direct build if the signal package is
    unavailable (standalone kernel use)."""
    def build():
        from ...core.exec_ir import adjoint_gather_steps
        gather, reduce_ = adjoint_gather_steps("vjp", plan, n_in, diag)
        m = reduce_.cin
        _, idx, pads, scale = plan_blocks(gather.plan, gather.diag,
                                          n_in, np.float32)
        return (idx, pads, scale, np.ones((m, 1), np.float32),
                lane_form(idx, n_in, 1, 1, plan.n_out))

    try:
        from ...signal import plan_cache_get
    except ImportError:
        return build()
    return plan_cache_get("vjp_adjoint", _digest(plan, diag, n_in),
                          build, backend=VJP_CACHE_BACKEND)


def _adjoint_dx(dg_flat: jax.Array, plan: ShufflePlan, n_in: int, diag,
                interpret: bool) -> jax.Array:
    """Run the cached adjoint lowering on a flat cotangent:
    (B, rows * t) -> (B, n_in)."""
    aidx, apads, ascale, ones, form = adjoint_lowering(plan, n_in, diag)
    dx = blocks_call(dg_flat, aidx, apads,
                     jnp.asarray(ones, dg_flat.dtype), interpret,
                     scale=ascale, form=form)
    return dx[..., 0]


def gemm_call(x: jax.Array, plan: ShufflePlan, w: jax.Array, rows: int,
              interpret: bool, diag, scopes=None, form=None) -> jax.Array:
    """:func:`repro.kernels.shuffle_gemm` body with a custom VJP.
    x: (..., n_in), w: (t, n_out) -> (..., rows, n_out).  ``form``: the
    plan's :func:`~.kernel.lane_form` (``reps = rows``), or ``None``."""
    t, idx, pads, scale = plan_blocks(plan, diag, rows, x.dtype)

    def impl(xb, w):
        return blocks_call(xb, idx, pads, w, interpret, scale, scopes,
                           form)

    def fwd(xb, w):
        return impl(xb, w), (xb, w)

    def bwd(res, dy):                       # dy: (B, rows, n_out)
        xb, w = res
        b, n_in = xb.shape
        n_out = w.shape[-1]
        # d_gathered = dy @ w.T — the transposed GEMM via the identity
        # gather (same kernel, operand transposed)
        iidx, ipads, iform = _identity_blocks(rows, n_out, rows)
        dg = blocks_call(dy.reshape(b, rows * n_out), iidx, ipads,
                         jnp.transpose(w), interpret, form=iform)
        # d_x — scatter-as-gather of the inverse index map (+ diag),
        # reduced on the array
        dx = _adjoint_dx(dg.reshape(b, rows * t), plan, n_in, diag,
                         interpret)
        # d_w — gathered activations against the cotangent (dense GEMM)
        g = apply_plan(xb, plan)
        if scale is not None:
            g = g * jnp.asarray(scale.reshape(-1), g.dtype)
        dw = jnp.einsum("brt,bro->to", g.reshape(b, rows, t),
                        dy.astype(g.dtype))
        return dx, dw.astype(w.dtype)

    op = jax.custom_vjp(impl)
    op.defvjp(fwd, bwd)
    batch = x.shape[:-1]
    out = op(x.reshape(-1, x.shape[-1]), w)
    return out.reshape(*batch, rows, w.shape[-1])


def grouped_call(x: jax.Array, plan: ShufflePlan, w: jax.Array,
                 reps: int, groups: int, nb: int, interpret: bool,
                 diag, scopes=None, form=None) -> jax.Array:
    """:func:`repro.kernels.shuffle_gemm_grouped` body with a custom
    VJP.  x: (..., n_in), w: (groups, t, n_out) -> (..., R * n_out)
    with R = reps * groups * nb.  ``form``: the plan's
    :func:`~.kernel.lane_form`, or ``None``."""
    rows = reps * groups * nb
    t, idx, pads, scale = plan_blocks(plan, diag, rows, x.dtype)

    def impl(xb, w):
        return shuffle_gemm_grouped_blocks(
            xb, jnp.asarray(idx), jnp.asarray(pads, dtype=xb.dtype), w,
            reps=reps, groups=groups, nb=nb, interpret=interpret,
            scale=None if scale is None else jnp.asarray(scale),
            scopes=scopes, form=form)

    def fwd(xb, w):
        return impl(xb, w), (xb, w)

    def bwd(res, dy):                       # dy: (B, R * n_out) flat
        xb, w = res
        b, n_in = xb.shape
        n_out = w.shape[-1]
        # d_gathered: the transposed grouped GEMM — identity gather,
        # per-group operand transposed.  Row r of the output block
        # holds dg[r, :] (length t), i.e. the plan-flat layout.
        iidx, ipads, iform = _identity_blocks(rows, n_out, reps, groups,
                                              nb)
        dg_flat = shuffle_gemm_grouped_blocks(
            dy, jnp.asarray(iidx), jnp.asarray(ipads, dy.dtype),
            jnp.transpose(w, (0, 2, 1)), reps=reps, groups=groups,
            nb=nb, interpret=interpret, form=iform)
        dx = _adjoint_dx(dg_flat, plan, n_in, diag, interpret)
        g = apply_plan(xb, plan)
        if scale is not None:
            g = g * jnp.asarray(scale.reshape(-1), g.dtype)
        dw = jnp.einsum(
            "brgnt,brgno->gto",
            g.reshape(b, reps, groups, nb, t),
            dy.reshape(b, reps, groups, nb, n_out).astype(g.dtype))
        return dx, dw.astype(w.dtype)

    op = jax.custom_vjp(impl)
    op.defvjp(fwd, bwd)
    batch = x.shape[:-1]
    out = op(x.reshape(-1, x.shape[-1]), w)
    return out.reshape(*batch, rows * w.shape[-1])
