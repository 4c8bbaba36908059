"""Pluggable execution backends for compiled SignalGraphs.

A :class:`~repro.core.exec_ir.ExecProgram` says *what* to execute — the
fused gather/einsum/lambda step sequence with plans, operands, masks and
param slots as data.  An :class:`ExecBackend` says *how*: it binds a
program to per-stage step executors once at compile time, and the shared
walker (:func:`repro.core.exec_ir.execute_program`) threads the stage
environment, multi-input combines and valid-frame masks identically for
every backend.

Two backends ship:

  * ``reference`` — interprets the step list with plain ``jnp`` ops
    (:func:`repro.core.exec_ir.run_steps_reference`): byte-for-byte the
    pre-backend execution path, differentiable, the parity oracle.
  * ``pallas`` — lowers each ``gather ∘ einsum (∘ post-shuffle)`` group
    onto the shuffle-GEMM array kernels, the software analogue of the
    paper's fabric feeding the computing array:

      - row-uniform einsums (FIR taps, DCT, mel, DWT banks) run through
        :func:`repro.kernels.shuffle_gemm` — the standalone gather ahead
        of the einsum AND the v2-folded ``pre``/``pre_diag`` stream
        shuffle compose into ONE XLA pass that writes the kernel's
        operand layout (Mosaic lowers no general in-VMEM gather, so the
        fabric pass is reported as emulated);
      - grouped einsums (the FFT butterfly: per-twiddle-class matmuls)
        run through :func:`repro.kernels.shuffle_gemm_grouped`;
      - each fabric pass of a kernel group — the stream-in and the
        einsum's stream-out ``post`` — runs as a reshape/transpose
        (route ``xla_transpose``) where its plan is a strided
        permutation (:func:`repro.core.fabric.strided_form`, decided on
        the host when the group is lowered and cached with it), else as
        a ``jnp.take`` gather (route ``jnp``);
      - steps named by a :class:`PrecisionPolicy` are *int-routed*: the
        gathered rows and the operand are symmetrically quantized
        (:mod:`repro.core.bitwidth`) and contracted exactly on the
        variable-bitwidth array via
        :func:`repro.kernels.bitserial_matmul`, then dequantized — the
        paper's 4/8/16-bit menu per array pass;
      - everything else (host lambdas, gathers feeding no array pass)
        is *emulated* on the reference path.

    Kernels run in interpret mode on CPU and compiled on real devices
    (:func:`repro.kernels.interpret_default`, env-overridable).  Both
    shuffle-GEMM kernels carry custom VJPs whose backward passes are
    themselves gather∘einsum groups on the same kernels
    (kernels/shuffle_gemm/vjp.py — the fabric is its own adjoint), and
    int-routed steps take a documented straight-through / dequantized
    gradient, so the backend is fully differentiable
    (``ExecBackend.differentiable``) and
    ``CompiledSignalGraph.value_and_grad`` trains on the array path.
    Backends that set ``differentiable = False`` make
    ``value_and_grad`` a hard error — training never silently changes
    backend.

:meth:`ExecBackend.bind` returns a :class:`BoundProgram` whose
``report()`` attributes every lowered step to its route — how many
fabric passes were actually fused into an array kernel vs emulated as an
XLA gather — surfaced per backend by
:func:`repro.core.perf_model.signal_graph_report`.

Backend-specific lowering artifacts are cached in the signal package's
keyed plan cache under the backend's name
(:func:`repro.signal.plan_cache_get`), so repeated compiles of the same
pipeline — offline, per-block streaming cores, serving buckets — reuse
one lowering, and :func:`repro.signal.plan_cache_info` exposes
per-backend hit/miss counts.
"""

from __future__ import annotations

import dataclasses
import hashlib
import math
from typing import Callable, Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..core import bitwidth as bw
from ..core import fabric
from ..core.exec_ir import (EinsumStep, ExecProgram, GatherStep,
                            execute_program, resolve_operand,
                            run_steps_reference, step_kind)
from ..core.fabric import (ShufflePlan, apply_plan, compose_into_einsum,
                           identity_plan)

__all__ = ["ExecBackend", "ReferenceBackend", "PallasBackend",
           "PrecisionPolicy", "BoundProgram", "StepRoute",
           "register_backend", "get_backend", "available_backends",
           "group_plan", "iter_step_groups", "classify_einsum",
           "bind_cached", "program_cache_key"]


# --------------------------------------------------------------------------
# Route accounting
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class StepRoute:
    """Where one lowered step executes under a backend.  ``route`` is one
    of ``fused_gemm`` / ``fused_grouped`` / ``int_bitserial`` (array
    kernels), ``jnp`` (emulated), ``xla_transpose`` (a gather step run
    as a layout copy ahead of its kernel), ``host`` (lambda glue);
    ``absorbed_gathers`` counts standalone fabric passes a kernel
    performs itself — none today: every kernel's gather is an XLA pass
    ahead of it, reported as its own gather route.  ``fabric`` lists
    how each fabric pass the step runs is lowered — ``xla_transpose``
    or ``jnp`` (a take) — stream-in first: a kernel group's passes sit
    on its einsum's route."""
    stage: str
    step: str
    kind: str                   # 'gather' | 'einsum' | 'lambda'
    route: str
    absorbed_gathers: int = 0
    fabric: Tuple[str, ...] = ()


def _routes_report(name: str, routes: Sequence[StepRoute]) -> dict:
    fabric_fused = sum(r.absorbed_gathers for r in routes)
    fabric_emulated = sum(1 for r in routes if r.kind == "gather"
                          and r.route in ("jnp", "xla_transpose"))
    lowering = {"xla_transpose": 0, "jnp": 0}
    for r in routes:
        for how in r.fabric:
            lowering[how] += 1
    array = [r for r in routes if r.kind == "einsum"]
    by_route: Dict[str, int] = {}
    for r in routes:
        by_route[r.route] = by_route.get(r.route, 0) + 1
    return {
        "name": name,
        "fabric_passes": {"fused": fabric_fused,
                          "emulated": fabric_emulated},
        "fabric_lowering": lowering,
        "array_passes": {
            "fused": sum(1 for r in array
                         if r.route in ("fused_gemm", "fused_grouped")),
            "int_routed": sum(1 for r in array
                              if r.route == "int_bitserial"),
            "emulated": sum(1 for r in array if r.route == "jnp"),
        },
        "host_steps": sum(1 for r in routes if r.kind == "lambda"),
        "routes": by_route,
    }


@dataclasses.dataclass
class BoundProgram:
    """A program bound to one backend: callable ``(x, params,
    valid_frames) -> outputs`` plus the per-step route attribution."""
    backend: "ExecBackend"
    program: ExecProgram
    stage_fns: Dict[str, Callable]
    routes: List[StepRoute]

    def __call__(self, x, params=None, valid_frames=None):
        return execute_program(self.program, self.stage_fns, x, params,
                               valid_frames)

    def report(self) -> dict:
        return _routes_report(self.backend.name, self.routes)


# --------------------------------------------------------------------------
# Precision policy (int routing through the variable-bitwidth array)
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PrecisionPolicy:
    """Per-step operand/activation bitwidths for the ``pallas`` backend.

    ``widths`` maps a stage name (or a fully-qualified step name such as
    ``"mel.mel"``) to ``(a_width, w_width)``; ``default`` optionally
    applies to every *row-uniform* einsum not named explicitly.  A
    matched step is int-routed: activations quantize per contraction row,
    the operand per output channel (symmetric,
    :func:`repro.core.bitwidth.quantize`), the integer contraction runs
    exactly on :func:`repro.kernels.bitserial_matmul`, and the result is
    dequantized with the product of scales — output error is pure
    quantization error, bounded by the chosen widths.  Routings whose
    accumulation could wrap the int32 array accumulator
    (``aw + ww - 2 + ceil(log2 K) > 31``) are rejected at bind time
    rather than silently wrapping.  Grouped (butterfly) einsums are
    never int-routed: their twiddle dynamic range is what the paper
    keeps in 16-bit."""
    widths: Mapping[str, Tuple[int, int]] = \
        dataclasses.field(default_factory=dict)
    default: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        # Collect every invalid entry before raising: a calibration- or
        # hand-built table with several bad rows reports them all in one
        # error instead of one per edit-rerun cycle.
        problems = []
        bad = [(key, (aw, ww)) for key, (aw, ww) in dict(self.widths).items()
               if aw not in bw.VALID_WIDTHS or ww not in bw.VALID_WIDTHS]
        if bad:
            listing = "; ".join(f"{key!r}: {w}" for key, w in bad)
            problems.append(
                f"PrecisionPolicy widths for {listing} must be from "
                f"{bw.VALID_WIDTHS}")
        if self.default is not None and (
                self.default[0] not in bw.VALID_WIDTHS
                or self.default[1] not in bw.VALID_WIDTHS):
            problems.append(f"invalid default widths {self.default}")
        if problems:
            raise ValueError("; ".join(problems))

    def widths_for(self, stage: str,
                   step: str) -> Optional[Tuple[int, int]]:
        """Most-specific match: step name, then stage name, then the
        default."""
        w = dict(self.widths)
        if step in w:
            return tuple(w[step])
        if stage in w:
            return tuple(w[stage])
        return None if self.default is None else tuple(self.default)

    def cache_token(self) -> Tuple:
        """Hashable identity for lowering-cache keys."""
        return (tuple(sorted((k, tuple(v))
                             for k, v in dict(self.widths).items())),
                None if self.default is None else tuple(self.default))


# --------------------------------------------------------------------------
# Einsum classification (which kernel shape a step maps onto)
# --------------------------------------------------------------------------

def _spec_axes(spec: str) -> Tuple[str, str, str]:
    lhs, out = spec.split("->")
    ins, op = lhs.split(",")
    return ins.replace("...", ""), op.replace("...", ""), \
        out.replace("...", "")


def _prod(xs) -> int:
    return int(math.prod(xs)) if xs else 1


@dataclasses.dataclass(frozen=True)
class _EinsumShape:
    """Canonical GEMM view of an EinsumStep: gathered rows reshape to
    ``(rows_total, t)`` and contract against a ``(t, cout)`` operand —
    shared across all rows (``groups == 1``) or per-group
    (``(groups, t, cout)``, rows in ``(reps, groups, nb)`` layout)."""
    rows_total: int
    t: int
    grouped: bool                # True => per-group operand (butterfly)
    groups: int
    reps: int
    nb: int
    op_perm: Tuple[int, ...]     # operand transpose to canonical order
    op_shape: Tuple[int, ...]    # canonical operand shape after reshape


def classify_einsum(step: EinsumStep) -> Optional[_EinsumShape]:
    """Map a step onto a kernel shape, or None when the spec falls
    outside the supported family (the backend then emulates it).

    Supported: the input reshapes to row axes followed by trailing
    contracted axes; the output keeps the row axes leading (input
    order) followed by the operand's output-only axes; the operand
    indexes the contracted and output-only axes plus at most ONE row
    axis (the *group* axis — the FFT butterfly's twiddle class)."""
    ins, op, out = _spec_axes(step.spec)
    if len(ins) != len(step.reshape_in) or len(set(ins)) != len(ins) \
            or len(set(op)) != len(op) or len(set(out)) != len(out):
        return None
    dims = dict(zip(ins, step.reshape_in))
    contracted = [c for c in ins if c not in out]
    if not contracted or list(ins[-len(contracted):]) != contracted:
        return None
    if step.out_rank != len(out):
        # the reference semantics flatten only the last out_rank axes of
        # the einsum result; the kernels flatten the whole suffix — only
        # equivalent when out_rank covers every output axis.
        return None
    rows_axes = [c for c in ins if c in out]
    out_only = [c for c in op if c not in ins]
    group_axes = [c for c in op if c in ins and c in out]
    if list(out) != rows_axes + out_only:
        return None
    if any(c not in op for c in contracted):
        return None          # contraction without an operand axis
    t = _prod([dims[c] for c in contracted])
    rows_total = _prod([dims[c] for c in rows_axes])
    if not group_axes:
        desired = contracted + out_only
        perm = tuple(op.index(c) for c in desired)
        return _EinsumShape(rows_total, t, False, 1, rows_total, 1,
                            perm, (t, -1))
    if len(group_axes) != 1:
        return None
    gax = group_axes[0]
    gi = ins.index(gax)
    reps = _prod([dims[c] for c in ins[:gi]])
    nb = _prod([dims[c] for c in ins[gi + 1:len(ins) - len(contracted)]])
    desired = [gax] + contracted + out_only
    perm = tuple(op.index(c) for c in desired)
    return _EinsumShape(rows_total, t, True, dims[gax], reps, nb, perm,
                        (dims[gax], t, -1))


def _operand_to_canonical(op_arr, shape: _EinsumShape, dtype):
    """Transpose/reshape an einsum operand into the kernel's canonical
    ``(t, cout)`` / ``(groups, t, cout)`` layout."""
    w = jnp.asarray(op_arr, dtype=dtype)
    w = jnp.transpose(w, shape.op_perm)
    return w.reshape(shape.op_shape)


def group_plan(e: EinsumStep, gather: Optional[GatherStep]
               ) -> Optional[Tuple[_EinsumShape, ShufflePlan, object]]:
    """Classify a ``(gather?) ∘ einsum`` pair as one fused kernel group.

    Returns ``(shape, plan, diag)`` — the canonical GEMM shape and the
    single composed fabric plan gathered ahead of the kernel — or ``None``
    when the spec is outside the kernel family or the plan's output
    length disagrees with the einsum's flat input.  This is the single
    source of truth for *which* step groups lower onto the array: the
    pallas backend's :meth:`PallasBackend._lower_group` and the SigQuant
    calibration observer (:mod:`repro.precision`) both route through it,
    so recorded ranges map one-to-one onto int-routable kernel calls."""
    shape = classify_einsum(e)
    if shape is None:
        return None
    n_in_flat = _prod(e.reshape_in)
    # compose the standalone gather and the v2-folded stream-in shuffle
    # into ONE plan, gathered once ahead of the kernel.
    if gather is not None:
        plan, diag = compose_into_einsum(gather.plan, gather.diag,
                                         e.pre, e.pre_diag)
    elif e.pre is not None:
        plan, diag = e.pre, e.pre_diag
    else:
        plan, diag = identity_plan(n_in_flat), e.pre_diag
    if plan.n_out != n_in_flat:
        return None
    return shape, plan, diag


def iter_step_groups(program: ExecProgram):
    """Yield ``(stage_name, gather, einsum, shape, plan, diag)`` for
    every step group the pallas backend would lower as one kernel call,
    walking stages with exactly the pairing rule of
    :meth:`PallasBackend.lower_stage`: an adjacent gather∘einsum pair
    groups when :func:`group_plan` accepts it, otherwise the einsum is
    tried alone.  The calibration observer iterates this to attach
    range statistics to precisely the steps a :class:`PrecisionPolicy`
    can name."""
    for st in program.stages:
        steps = st.steps
        i = 0
        while i < len(steps):
            s = steps[i]
            nxt = steps[i + 1] if i + 1 < len(steps) else None
            if isinstance(s, GatherStep) and isinstance(nxt, EinsumStep):
                g = group_plan(nxt, s)
                if g is not None:
                    yield (st.name, s, nxt, *g)
                    i += 2
                    continue
            if isinstance(s, EinsumStep):
                g = group_plan(s, None)
                if g is not None:
                    yield (st.name, None, s, *g)
            i += 1


# --------------------------------------------------------------------------
# Backends
# --------------------------------------------------------------------------

class ExecBackend:
    """Base class: subclasses implement :meth:`lower_stage`.  ``bind``
    lowers every stage once (compile time) and returns the bound
    program; ``cache_key`` keys compile caches (streaming cores, serving
    buckets) so two backends never share a compiled program slot."""

    name = "base"
    differentiable = False
    # bindings are shared through the fingerprint-keyed compile cache
    # (bind_cached) unless a backend opts out — backends carrying
    # per-instance mutable state (the calibration observer writes into
    # its own CalibrationRecord) must bind privately or a second
    # instance would execute through the first's closures.
    bind_cacheable = True

    @property
    def cache_key(self) -> Tuple:
        return (self.name,)

    def lower_stage(self, stage) -> Tuple[Callable, List[StepRoute]]:
        raise NotImplementedError

    def bind(self, program: ExecProgram) -> BoundProgram:
        stage_fns: Dict[str, Callable] = {}
        routes: List[StepRoute] = []
        for st in program.stages:
            fn, rs = self.lower_stage(st)
            stage_fns[st.name] = fn
            routes.extend(rs)
        return BoundProgram(self, program, stage_fns, routes)


def program_cache_key(backend: ExecBackend,
                      program: ExecProgram) -> Optional[Tuple]:
    """The fingerprint-keyed compile-cache key for one (backend,
    program) pair, or ``None`` when the program has no fingerprint
    (opaque lambda closure — never shared).  Combines the program's
    structural digest with the backend's ``cache_key`` (name,
    interpret mode, precision-policy token), so two structurally
    identical programs share a slot only under the same lowering
    configuration."""
    fp = program.fingerprint()
    if fp is None:
        return None
    return (backend.cache_key, fp)


def bind_cached(backend: ExecBackend,
                program: ExecProgram) -> BoundProgram:
    """Bind through the fingerprint-keyed compile cache.

    Two compiles whose programs carry the same structural fingerprint
    under the same backend configuration share ONE :class:`BoundProgram`
    — one stage-lowering pass, one set of kernel closures — instead of
    re-lowering per registered graph name.  The shared bound program is
    a pure function of the fingerprint (lambda content included), so
    executing graph B through graph A's binding is exact.  Programs
    without a fingerprint bind privately, as before.  Hits/misses count
    in the plan-cache stats under the backend's name
    (:func:`repro.signal.plan_cache_info`)."""
    if not backend.bind_cacheable:
        return backend.bind(program)
    key = program_cache_key(backend, program)
    if key is None:
        return backend.bind(program)
    from . import plan_cache_get
    return plan_cache_get("bound_program", key,
                          lambda: backend.bind(program),
                          backend=backend.name)


class ReferenceBackend(ExecBackend):
    """The pre-backend jnp interpreter, byte-for-byte: every gather is an
    XLA ``take``/``where``, every array pass a ``jnp.einsum``.  This is
    the parity oracle and the differentiation path."""

    name = "reference"
    differentiable = True

    def lower_stage(self, stage):
        steps = stage.steps
        routes = []
        for s in steps:
            kind = step_kind(s)
            routes.append(StepRoute(stage.name, s.name, kind,
                                    "host" if kind == "lambda" else "jnp"))

        def run(x, sp):
            return run_steps_reference(steps, x, sp)
        return run, routes


class PallasBackend(ExecBackend):
    """Lower gather∘einsum(∘post) groups onto the Pallas array kernels.

    ``interpret=None`` resolves via
    :func:`repro.kernels.interpret_default` at bind time (interpret on
    CPU/CI, compiled on devices); ``precision`` optionally int-routes
    named steps through :func:`repro.kernels.bitserial_matmul` (see
    :class:`PrecisionPolicy`).

    Differentiable: the shuffle-GEMM kernels carry custom VJPs that run
    the backward pass on the same fabric+array machinery
    (kernels/shuffle_gemm/vjp.py), and int-routed steps take the
    straight-through / dequantized gradient (see :meth:`_int_unit`)."""

    name = "pallas"
    differentiable = True

    def __init__(self, interpret: Optional[bool] = None,
                 precision: Optional[PrecisionPolicy] = None):
        self.interpret = interpret
        self.precision = precision or PrecisionPolicy()

    @property
    def cache_key(self) -> Tuple:
        return (self.name, self.interpret, self.precision.cache_token())

    def _interpret(self) -> bool:
        if self.interpret is None:
            from ..kernels import interpret_default
            return interpret_default()
        return bool(self.interpret)

    # -- lowering -----------------------------------------------------------
    def lower_stage(self, stage):
        # (unit, step names): a lowered group unit takes the names of its
        # (gather, einsum) steps at call time for its named scopes, since
        # the cached unit is shared by steps of equal content
        units: List[Tuple[Callable, Tuple[Optional[str], str]]] = []
        routes: List[StepRoute] = []
        steps = stage.steps
        i = 0
        while i < len(steps):
            s = steps[i]
            nxt = steps[i + 1] if i + 1 < len(steps) else None
            if isinstance(s, GatherStep) and isinstance(nxt, EinsumStep):
                unit = self._lower_group(stage.name, nxt, gather=s)
                if unit is not None:
                    fn, route = unit
                    units.append((fn, (s.name, nxt.name)))
                    # the group's gather runs as an XLA pass ahead of
                    # the array kernel (no kernel gathers in VMEM): the
                    # fabric pass is emulated, not fused.
                    routes.append(StepRoute(stage.name, s.name,
                                            "gather", route.fabric[0]))
                    routes.append(route)
                    i += 2
                    continue
            if isinstance(s, EinsumStep):
                unit = self._lower_group(stage.name, s, gather=None)
                if unit is not None:
                    fn, route = unit
                    units.append((fn, (None, s.name)))
                    routes.append(route)
                    i += 1
                    continue
            kind = step_kind(s)
            routes.append(StepRoute(stage.name, s.name, kind,
                                    "host" if kind == "lambda" else "jnp",
                                    fabric=_reference_passes(s)))
            units.append((_reference_unit(s), None))
            i += 1
        _count_fabric_passes(routes)

        def run(x, sp):
            for u, names in units:
                x = u(x, sp, names)
            return x
        return run, routes

    def _lower_group(self, stage_name: str, e: EinsumStep,
                     gather: Optional[GatherStep]):
        """One fused kernel call for (gather?) ∘ einsum ∘ (post?), or
        None when the einsum spec is outside the kernel family (the
        caller then falls back to the reference path step by step)."""
        g = group_plan(e, gather)
        if g is None:
            return None
        shape, plan, diag = g
        widths = self.precision.widths_for(stage_name, e.name)
        if widths is not None and not shape.grouped:
            _check_int_headroom(e.name, widths, shape.t)
        interpret = self._interpret()

        def build():
            if widths is not None and not shape.grouped:
                return (self._int_unit(e, shape, plan, diag, widths,
                                       interpret), "int_bitserial",
                        ("jnp",) * (1 + (e.post is not None)))
            from ..kernels.shuffle_gemm.kernel import lane_form
            # the fabric passes' lowerings, decided here on the host
            # (the plans are numpy) and cached with the group
            form_in = lane_form(plan.gather_idx.reshape(shape.rows_total,
                                                        -1),
                                shape.reps, shape.groups, shape.nb,
                                plan.n_out)
            form_out = None if e.post is None else \
                fabric.strided_form(e.post.gather_idx, e.post.n_out)
            forms = (form_in,) if e.post is None else (form_in, form_out)
            passes = tuple("jnp" if f is None else "xla_transpose"
                           for f in forms)
            if not shape.grouped:
                return self._gemm_unit(e, shape, plan, diag, interpret,
                                       form_in, form_out), \
                    "fused_gemm", passes
            return self._grouped_unit(e, shape, plan, diag, interpret,
                                      form_in, form_out), \
                "fused_grouped", passes

        key = _group_digest(e, plan, diag, widths, interpret)
        from . import plan_cache_get
        fn, route_name, passes = plan_cache_get("exec_group", key, build,
                                                backend=self.name)
        return fn, StepRoute(stage_name, e.name, "einsum", route_name,
                             fabric=passes)

    # -- unit builders ------------------------------------------------------
    def _gemm_unit(self, e: EinsumStep, shape: _EinsumShape,
                   plan: ShufflePlan, diag, interpret: bool, form_in,
                   form_out):
        from ..kernels.shuffle_gemm.vjp import gemm_call
        post = e.post

        def unit(x, sp, names):
            gather, einsum, out = _group_scopes(names)
            with jax.named_scope(einsum):
                w = _operand_to_canonical(resolve_operand(e, sp), shape,
                                          x.dtype)
            y = gemm_call(x, plan, w, shape.rows_total, interpret, diag,
                          (gather, einsum), form_in)
            with jax.named_scope(einsum):
                y = y.reshape(*y.shape[:-2], -1)
            if post is None:
                return y
            with jax.named_scope(out):
                return _stream_out(y, post, form_out)
        return unit

    def _grouped_unit(self, e: EinsumStep, shape: _EinsumShape,
                      plan: ShufflePlan, diag, interpret: bool, form_in,
                      form_out):
        from ..kernels.shuffle_gemm.vjp import grouped_call
        post = e.post

        def unit(x, sp, names):
            gather, einsum, out = _group_scopes(names)
            with jax.named_scope(einsum):
                w = _operand_to_canonical(resolve_operand(e, sp), shape,
                                          x.dtype)
            y = grouped_call(x, plan, w, shape.reps, shape.groups,
                             shape.nb, interpret, diag, (gather, einsum),
                             form_in)
            if post is None:
                return y
            with jax.named_scope(out):
                return _stream_out(y, post, form_out)
        return unit

    def _int_unit(self, e: EinsumStep, shape: _EinsumShape,
                  plan: ShufflePlan, diag, widths: Tuple[int, int],
                  interpret: bool):
        """Int-routed GEMM with a straight-through / dequantized
        gradient.

        Forward: symmetric per-channel quantization, exact bitserial
        integer contraction, dequantization.  ``round`` is
        piecewise-constant — zero gradient almost everywhere — so
        differentiating the literal forward would silently kill
        training through any int-routed step.  The deliberate policy
        (the straight-through estimator over the whole
        quantize→matmul→dequantize block) is: the backward pass is the
        float GEMM's VJP evaluated at the *unquantized* residuals, with
        the upstream cotangent taken at the quantized output.
        Equivalent formulation: ``y = y_float + stop_gradient(y_int -
        y_float)`` — exactly what tests/test_pallas_vjp.py pins down.
        """
        from ..kernels import bitserial_matmul
        aw, ww = widths
        post = e.post

        def int_fwd(h, w):
            xq, x_scale = bw.quantize(h, aw, axis=-1)
            wq, w_scale = bw.quantize(w, ww, axis=0)
            acc = bitserial_matmul(xq.astype(jnp.int32),
                                   wq.astype(jnp.int32), aw, ww,
                                   interpret=interpret)
            return acc.astype(jnp.float32) * x_scale * w_scale

        def st_fwd(h, w):
            return int_fwd(h, w), (h, w)

        def st_bwd(res, dy):
            h, w = res
            dh = jnp.einsum("...rc,tc->...rt", dy, w).astype(h.dtype)
            hb = h.reshape(-1, *h.shape[-2:])
            dyb = dy.reshape(-1, *dy.shape[-2:]).astype(h.dtype)
            dw = jnp.einsum("brt,brc->tc", hb, dyb)
            return dh, dw.astype(w.dtype)

        int_op = jax.custom_vjp(int_fwd)
        int_op.defvjp(st_fwd, st_bwd)

        def unit(x, sp, names):
            gather, einsum, out = _group_scopes(names)
            with jax.named_scope(gather):
                g = apply_plan(x, plan)
                if diag is not None:
                    g = g * jnp.asarray(diag, dtype=g.dtype)
            with jax.named_scope(einsum):
                h = g.reshape(*g.shape[:-1], shape.rows_total, shape.t)
                w = _operand_to_canonical(resolve_operand(e, sp), shape,
                                          jnp.float32)
                y = int_op(h.astype(jnp.float32), w).astype(x.dtype)
                y = y.reshape(*y.shape[:-2], -1)
            if post is None:
                return y
            with jax.named_scope(out):
                return apply_plan(y, post)
        return unit


def _check_int_headroom(step_name: str, widths: Tuple[int, int],
                        k: int) -> None:
    """Reject precision-policy routings whose integer accumulation can
    wrap the array's 32-bit accumulator: each quantized product is
    < 2^(aw+ww-2) and ``k`` of them sum per output, so the policy needs
    ``aw + ww - 2 + ceil(log2 k) <= 31``.  Failing loudly at bind time
    beats silently wrapped (sign-flipped) outputs."""
    aw, ww = widths
    need = bw.int_headroom_bits(aw, ww, k)
    if need > bw.ACC_BITS:
        raise ValueError(
            f"PrecisionPolicy({aw}, {ww}) on step {step_name!r} with "
            f"contraction size {k} needs {need} accumulator bits and "
            f"would overflow the int32 array accumulator; choose "
            f"narrower widths (aw + ww - 2 + ceil(log2 K) must be "
            f"<= 31)")


def _reference_unit(step):
    def unit(x, sp, names):
        return run_steps_reference([step], x, sp)   # scopes its own step
    return unit


def _reference_passes(step) -> Tuple[str, ...]:
    """The fabric passes a step on the reference path runs, each a
    ``jnp.take``: a gather step's plan, an einsum's ``pre``/``post``."""
    if isinstance(step, GatherStep):
        return ("jnp",)
    if isinstance(step, EinsumStep):
        return ("jnp",) * ((step.pre is not None) + (step.post is not None))
    return ()


def _count_fabric_passes(routes: Sequence[StepRoute]) -> None:
    """Count each lowered fabric pass by its lowering, at bind:
    ``fabric.strided_passes`` (a layout copy) or ``fabric.take_passes``
    (a gather)."""
    m = obs.metrics()
    for r in routes:
        for how in r.fabric:
            m.counter("fabric.strided_passes" if how == "xla_transpose"
                      else "fabric.take_passes").inc()


def _stream_out(y, post: ShufflePlan, form):
    """An einsum's stream-out permutation: the layout copy of its
    :func:`~repro.core.fabric.strided_form` where that spans ``y``,
    else the gather."""
    if form is not None and y.shape[-1] == math.prod(form[0]):
        return fabric.apply_strided(y, form)
    return apply_plan(y, post)


def _group_scopes(names: Tuple[Optional[str], str]) -> Tuple[str, str, str]:
    """Named scopes of one lowered group from its (gather, einsum) step
    names: the gather feeding the array (the gather step's, else the
    einsum's own stream-in permutation), the array pass, and the
    einsum's stream-out permutation."""
    gather, einsum = names
    return (f"gather:{gather or einsum}", f"einsum:{einsum}",
            f"gather:{einsum}")


def _group_digest(e: EinsumStep, plan: ShufflePlan, diag,
                  widths, interpret: bool) -> Tuple:
    """Content digest of one lowered group: everything the built unit
    closure depends on.  Lambdas never reach here, so cached units are
    pure functions of this key and safe to share across programs."""
    h = hashlib.sha1()
    for arr in (plan.gather_idx, plan.pad_values,
                np.asarray(diag) if diag is not None else np.zeros(0),
                np.asarray(e.operand),
                e.post.gather_idx if e.post is not None else np.zeros(0),
                e.post.pad_values if e.post is not None else np.zeros(0)):
        a = np.ascontiguousarray(arr)
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    meta = (e.spec, tuple(e.reshape_in), e.out_rank, e.rows, e.cin,
            e.cout, e.param_key, widths, bool(interpret))
    return (h.hexdigest(), meta)


# --------------------------------------------------------------------------
# Registry
# --------------------------------------------------------------------------

_BACKENDS: Dict[str, Callable[[], ExecBackend]] = {
    "reference": ReferenceBackend,
    "pallas": PallasBackend,
}


def register_backend(name: str,
                     factory: Callable[[], ExecBackend]) -> None:
    """Register a backend factory under ``name`` (resolved by
    :func:`get_backend` / ``compile(backend=name)``)."""
    _BACKENDS[name] = factory


def available_backends() -> List[str]:
    return sorted(_BACKENDS)


def get_backend(backend) -> ExecBackend:
    """Resolve a backend name to a fresh instance, or pass an
    :class:`ExecBackend` instance through (custom interpret / precision
    configurations)."""
    if isinstance(backend, ExecBackend):
        return backend
    try:
        return _BACKENDS[backend]()
    except KeyError:
        raise ValueError(
            f"unknown execution backend {backend!r}; choose from "
            f"{available_backends()} or pass an ExecBackend instance")
