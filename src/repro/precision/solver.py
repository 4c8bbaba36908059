"""SigQuant width solver: greedy narrow-then-repair over the 4/8/16 menu.

Given a :class:`~repro.precision.calibration.CalibrationRecord`, pick
per-step ``(a_width, w_width)`` from :data:`LADDER` (cheapest-first by
array throughput) such that

* no step can overflow the int32 array accumulator — a candidate width
  is *admissible* only when :meth:`StepStats.fits` proves it from both
  the worst-case static bound and the recorded-range bound;
* every declared output's relative L2 error against the fp32 reference,
  measured on the **held-out** batches through the real pallas int
  route, stays within ``budget``.

Strategy (narrow-then-repair): start every step at its narrowest
admissible widths, evaluate the candidate policy end to end, and while
any output exceeds the budget, widen one step — the one with the
largest recorded *local* fake-quant error among those reaching the
worst output — then re-evaluate.  Evaluation uses
``compiled.with_backend(PallasBackend(precision=...))``: the solver
scores exactly the kernels serving will run, not a proxy.  Steps with
no admissible widths (contraction too large even for ``(4, 4)``) are
left off the policy and stay on the float kernels.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..signal.backends import PallasBackend, PrecisionPolicy
from .calibration import LADDER, CalibrationRecord, calibrate

__all__ = ["solve_widths", "auto_policy", "policy_errors", "LADDER"]


def _as_dict(compiled, out) -> Dict[str, np.ndarray]:
    if isinstance(out, dict):
        return {k: np.asarray(v) for k, v in out.items()}
    return {compiled.outputs[0]: np.asarray(out)}


def policy_errors(record: CalibrationRecord,
                  policy: Optional[PrecisionPolicy],
                  interpret: Optional[bool] = None) -> Dict[str, float]:
    """Worst per-output relative L2 error of ``policy`` on the record's
    held-out batches, evaluated through the real pallas route (int
    kernels for routed steps, float kernels otherwise)."""
    compiled = record.compiled
    bound = compiled.with_backend(
        PallasBackend(interpret=interpret, precision=policy))
    fn = bound.jit()
    errs: Dict[str, float] = {}
    for batch, base in zip(record.holdout, record.baselines):
        outs = _as_dict(compiled, fn(jnp.asarray(batch), record.params))
        bases = _as_dict(compiled, base)
        for name, ref in bases.items():
            y = outs[name]
            denom = max(float(np.sqrt((np.abs(ref) ** 2).mean())), 1e-12)
            err = float(np.sqrt((np.abs(y - ref) ** 2).mean())) / denom
            errs[name] = max(errs.get(name, 0.0), err)
    return errs


def solve_widths(record: CalibrationRecord, budget: float = 1e-2,
                 ladder: Sequence[Tuple[int, int]] = LADDER,
                 interpret: Optional[bool] = None,
                 max_rounds: int = 64) -> PrecisionPolicy:
    """Solve per-step widths meeting ``budget`` on every output; returns
    a :class:`PrecisionPolicy` naming every admissible GEMM-shaped step.
    Raises ``ValueError`` when the budget is unreachable even with every
    step at its widest admissible widths."""
    with obs.span("SigQuant", "quant.solve_widths", graph=record.graph,
                  budget=budget) as sp:
        return _solve(record, budget, ladder, interpret, max_rounds, sp)


def _solve(record, budget, ladder, interpret, max_rounds,
           sp) -> PrecisionPolicy:
    admissible = {
        name: [tuple(p) for p in ladder if record.steps[name].fits(p)]
        for name in record.gemm_steps()}
    admissible = {n: ps for n, ps in admissible.items() if ps}
    if not admissible:
        return PrecisionPolicy()
    level = {n: 0 for n in admissible}

    def current() -> PrecisionPolicy:
        return PrecisionPolicy(widths={n: admissible[n][level[n]]
                                       for n in admissible})

    for _ in range(max_rounds):
        policy = current()
        errs = policy_errors(record, policy, interpret=interpret)
        worst = max(errs, key=lambda k: errs[k])
        if errs[worst] <= budget:
            record.assert_no_overflow(policy)
            sp.set(steps=len(admissible), worst_err=errs[worst])
            return policy
        grow = [n for n in admissible
                if level[n] + 1 < len(admissible[n])
                and worst in record.steps[n].reaches]
        if not grow:       # nothing reaching the worst output can widen
            grow = [n for n in admissible
                    if level[n] + 1 < len(admissible[n])]
        if not grow:
            raise ValueError(
                f"width solver cannot meet the {budget:g} error budget "
                f"for output {worst!r} (error {errs[worst]:.3g}) — every "
                f"int-routable step is already at its widest admissible "
                f"widths; raise the budget or leave steps on the float "
                f"kernels")

        def local(name: str) -> float:
            st = record.steps[name]
            return st.local_err.get(admissible[name][level[name]], 0.0)

        level[max(grow, key=local)] += 1
    raise ValueError(
        f"width solver did not converge in {max_rounds} rounds")


def auto_policy(compiled, batches, params=None, budget: float = 1e-2,
                holdout=None, ladder: Sequence[Tuple[int, int]] = LADDER,
                interpret: Optional[bool] = None
                ) -> Tuple[PrecisionPolicy, CalibrationRecord]:
    """Calibrate-then-solve convenience: observe ``batches`` through
    ``compiled`` and return ``(policy, record)`` meeting ``budget``."""
    record = calibrate(compiled, batches, params=params,
                       holdout=holdout, ladder=ladder)
    policy = solve_widths(record, budget=budget, ladder=ladder,
                          interpret=interpret)
    return policy, record
