"""Public wrappers: run a compiled ShufflePlan + GEMM through the
shuffle-GEMM Pallas kernels.  Accepts the same ShufflePlan objects as
core.fabric.

Both ops carry a custom VJP (vjp.py): the transpose of a gather∘einsum
group is another gather∘einsum group, so reverse-mode differentiation
stays on the same fabric+kernel machinery — ``jax.grad`` through either
op never leaves the array path.
"""

from __future__ import annotations

from typing import Optional

import jax

from ...core.fabric import ShufflePlan
from .kernel import lane_form
from .vjp import gemm_call, grouped_call


def _resolve_interpret(interpret: Optional[bool]) -> bool:
    from .. import resolve_interpret
    return resolve_interpret(interpret)


def shuffle_gemm(x: jax.Array, plan: ShufflePlan, w: jax.Array,
                 rows: int, interpret: Optional[bool] = None,
                 diag=None, scopes=None) -> jax.Array:
    """out = reshape(apply_plan(x) (* diag), (rows, t)) @ w: an XLA
    fabric pass feeding one GEMM kernel — a transpose where the plan is
    a strided permutation of ``x``, else a gather (kernel.py).

    x: (..., n_in); plan.n_out == rows * t; w: (t, n_out); diag is an
    optional per-element scale of the gathered stream (a GatherStep /
    EinsumStep ``diag``).  Returns (..., rows, n_out).  ``interpret=None``
    resolves via :func:`repro.kernels.interpret_default`.  ``scopes``
    optionally names the gather's and the kernel call's ``jax``
    named scopes (a ``(gather, kernel)`` pair of strings).

    Differentiable in ``x`` and ``w`` via a custom VJP whose backward
    pass runs on the same kernels (see shuffle_gemm/vjp.py).
    """
    form = lane_form(plan.gather_idx.reshape(rows, -1), rows, 1, 1,
                     x.shape[-1])
    return gemm_call(x, plan, w, rows, _resolve_interpret(interpret),
                     diag, scopes, form)


def shuffle_gemm_grouped(x: jax.Array, plan: ShufflePlan, w: jax.Array,
                         reps: int, groups: int, nb: int,
                         interpret: Optional[bool] = None,
                         diag=None, scopes=None) -> jax.Array:
    """Grouped-operand variant: plan rows have flat layout
    ``(reps, groups, nb)`` and row ``r`` contracts against
    ``w[(r // nb) % groups]`` — the FFT-butterfly shape (per-twiddle-class
    matmuls) behind an arbitrary fused gather plan.

    x: (..., n_in); plan.n_out == reps * groups * nb * t;
    w: (groups, t, n_out).  Returns the flat (..., R * n_out) result in
    row order (the consuming einsum's natural layout).

    Differentiable in ``x`` and ``w`` via a custom VJP (vjp.py);
    ``scopes`` as in :func:`shuffle_gemm`.
    """
    form = lane_form(plan.gather_idx.reshape(reps * groups * nb, -1),
                     reps, groups, nb, x.shape[-1])
    return grouped_call(x, plan, w, reps, groups, nb,
                        _resolve_interpret(interpret), diag, scopes, form)
