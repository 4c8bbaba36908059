"""The program's own spans in a profiler trace, and what the per-layer
readers make of them.

``repro.obs.span`` writes each timed block of the program as a
``TraceAnnotation`` named ``repro.<name>`` on the host plane while a
profiler session records, its args (``wave``, ``bytes``, ...) as event
stats; a wave is ``repro.sched.dispatch`` around ``repro.wave``, which
holds ``repro.wave.stack``, ``.h2d``, ``.launch``, ``.fetch`` and
``.finish``.  Device ops map to the graph step they run for through the
``op_name`` metadata of the compiled program's HLO text, where each step
runs under the named scope ``<kind>:<step name>``.

    python3 -m bench.program_spans <trace dir or .xplane.pb> [<hlo text>]

prints the milliseconds per wave of each ``repro.*`` span and the idle
device seconds by the innermost ``repro.*`` span open during each gap
(inside the ``bench.window`` span where the trace has one), and with the
program's HLO text the device milliseconds per wave under ``gather:``
scopes and the share of device op time that maps to the text.
"""

from __future__ import annotations

import dataclasses
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

import numpy as np

from bench import trace as tr

PREFIX = "repro."
MODULES_LINE = "XLA Modules"
GATHER_SCOPE = "gather:"
_INSTR = re.compile(r"^\s*(ROOT\s+)?(%[^\s=]+) = ")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?(%[^\s(]+) ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=(%[\w.-]+)")


@dataclasses.dataclass
class Span(tr.Event):
    args: Dict[str, object] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Program:
    spans: List[Span]                       # repro.* host spans, by start
    modules: Dict[str, List[tr.Event]]      # device plane -> XLA modules

    def named(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name]


_LOADED: Dict[Tuple[str, float], Program] = {}


def load(path: str) -> Program:
    """The ``repro.*`` host spans and the device planes' module events of
    an ``.xplane.pb`` file, or of the newest one under a trace directory;
    read once per file."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = tr.find_xplane(path)
    key = (os.path.abspath(path), os.path.getmtime(path))
    if key in _LOADED:
        return _LOADED[key]
    spans: List[Span] = []
    modules: Dict[str, List[tr.Event]] = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name == tr.HOST_PLANE:
            for line in plane.lines:
                spans.extend(Span(e.name, float(e.start_ns),
                                  float(e.duration_ns), dict(e.stats))
                             for e in line.events
                             if e.name.startswith(PREFIX))
        elif plane.name.startswith(tr.DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    modules.setdefault(plane.name, []).extend(
                        tr.Event(e.name, float(e.start_ns),
                                 float(e.duration_ns)) for e in line.events)
    spans.sort(key=lambda s: s.start_ns)
    _LOADED[key] = Program(spans, modules)
    return _LOADED[key]


# -- the compiled program's text ------------------------------------------------

@dataclasses.dataclass
class HloText:
    module: str                     # the HloModule's name (jit_call)
    op_names: Dict[str, str]        # %instruction -> op_name ("" if none)


def parse_hlo(text: str) -> HloText:
    """Each instruction's ``op_name``; an instruction with none (a fusion
    XLA made) takes its called computation's root's."""
    module = text.split(None, 2)[1].rstrip(",")
    own: Dict[str, str] = {}
    calls: Dict[str, str] = {}
    roots: Dict[str, str] = {}
    comp = ""
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c is not None and line.rstrip().endswith("{"):
                comp = c.group(1)
            continue
        name = m.group(2)
        op = _OP_NAME.search(line)
        own[name] = op.group(1) if op else ""
        called = _CALLS.search(line)
        if called:
            calls[name] = called.group(1)
        if m.group(1):
            roots[comp] = name
    op_names = {}
    for name, op in own.items():
        if not op and name in calls:
            op = own.get(roots.get(calls[name], ""), "")
        op_names[name] = op
    return HloText(module, op_names)


def under_scope(op_name: str, prefix: str = GATHER_SCOPE) -> bool:
    """True when a scope of ``op_name`` starts with ``prefix``."""
    return any(part.startswith(prefix) for part in op_name.split("/"))


def program_ops(trace: tr.Trace, prog: Program, hlo: HloText,
                t0: float, t1: float):
    """The device ops of [t0, t1] as ``(event, op_name or None)``:
    None for an op outside the program's module runs or missing from its
    text."""
    out = []
    for dev, evs in trace.device_ops.items():
        runs = tr.merge([(m.start_ns, m.end_ns)
                         for m in prog.modules.get(dev, [])
                         if m.name.split("(")[0] == hlo.module])
        evs = [e for e in evs if t0 <= e.start_ns and e.end_ns <= t1]
        # the module run each op starts in, if any, must hold all of it
        i = np.searchsorted(runs[:, 0], [e.start_ns for e in evs],
                            side="right") - 1
        for e, k in zip(evs, i):
            inside = k >= 0 and e.end_ns <= runs[k, 1]
            name = e.name.split(" ", 1)[0]
            out.append((e, hlo.op_names.get(name) if inside else None))
    return out


def program_texts(run) -> Dict[Tuple[int, int], str]:
    """Optimised HLO text of each (bucket, rows) program the run's window
    executed: a service built like the harness's, its masked bucket
    program compiled after the window (from the compile cache).  Only
    the readers that need a program's text call this."""
    import jax
    import jax.numpy as jnp
    from repro.serving import SignalService

    cfg, mod = run.cfg, run.mod
    svc = SignalService(**cfg["service"])
    svc.register(cfg["name"], mod.build_graph(cfg))
    params = jax.eval_shape(lambda: mod.make_params(cfg, 0))
    texts = {}
    for bucket, rows in sorted({(w["bucket"], len(w["lens"]))
                                for w in run.record.waves if w["lens"]}):
        compiled = svc.compiled_for(cfg["name"], bucket)
        x = jax.ShapeDtypeStruct((rows, bucket), jnp.float32)
        vf = jax.ShapeDtypeStruct((rows,), jnp.int32)
        texts[bucket, rows] = (compiled.masked_jit().lower(x, vf, params)
                               .compile().as_text())
    return texts


# -- per-wave readers -------------------------------------------------------------

def _trace_dir() -> str:
    from bench import harness
    return harness.TRACE_DIR


def _window_spans(run, name: str) -> Optional[List[Span]]:
    """``name`` spans that start in the window, or None where the trace
    holds no ``repro.wave`` span there (a program without the spans)."""
    try:
        prog = load(_trace_dir())
    except FileNotFoundError:
        return None
    t0, t1 = tr.window(run.trace)
    inside = [s for s in prog.spans if t0 <= s.start_ns <= t1]
    if not any(s.name == "repro.wave" for s in inside):
        return None
    return [s for s in inside if s.name == name]


def _waves(run) -> int:
    return len(_window_spans(run, "repro.wave") or ())


def ms_per_wave(run, name: str) -> Optional[float]:
    """Summed length of the window's ``name`` spans per wave, in ms."""
    spans = _window_spans(run, name)
    if spans is None:
        return None
    return sum(s.dur_ns for s in spans) / _waves(run) / 1e6


def host_ms_per_wave(run, name: str) -> Optional[float]:
    """As :func:`ms_per_wave`, less the device busy time inside the
    spans."""
    spans = _window_spans(run, name)
    if spans is None or not run.trace.device_ops:
        return None
    merged = run.trace.busy(next(iter(run.trace.device_ops)))
    host = sum(s.dur_ns - tr.covered(merged, s.start_ns, s.end_ns)
               for s in spans)
    return host / _waves(run) / 1e6


def self_ms_per_wave(run, name: str, child: str) -> Optional[float]:
    """As :func:`ms_per_wave`, less the ``child`` spans inside each."""
    spans = _window_spans(run, name)
    if spans is None:
        return None
    kids = _window_spans(run, child)
    own = sum(s.dur_ns - sum(k.dur_ns for k in kids
                             if s.start_ns <= k.start_ns
                             and k.end_ns <= s.end_ns) for s in spans)
    return own / _waves(run) / 1e6


def scoped_device_ms_per_wave(run, texts: Optional[List[str]] = None,
                              prefix: str = GATHER_SCOPE
                              ) -> Optional[float]:
    """Device time of the window's ops whose ``op_name`` lies under a
    ``prefix`` scope, per wave, in ms (the run's programs' HLO text
    from :func:`program_texts` unless given)."""
    if _window_spans(run, "repro.wave") is None:
        return None
    t0, t1 = tr.window(run.trace)
    prog = load(_trace_dir())
    total = 0.0
    for text in texts if texts is not None else program_texts(run).values():
        for e, op_name in program_ops(run.trace, prog, parse_hlo(text),
                                      t0, t1):
            if op_name and under_scope(op_name, prefix):
                total += e.dur_ns
    return total / _waves(run) / 1e6


# -- idle device time by innermost span ------------------------------------------

def innermost(spans: List[tr.Event]) -> List[Tuple[float, float, str]]:
    """Disjoint ``(start, end, name)`` pieces of time, each labelled by
    the innermost of ``spans`` open in it (the latest begun that has not
    ended)."""
    out: List[Tuple[float, float, str]] = []
    stack: List[tr.Event] = []
    t = 0.0

    def close(until: float) -> None:
        nonlocal t
        while stack and stack[-1].end_ns <= until:
            top = stack.pop()
            if top.end_ns > t:
                out.append((t, top.end_ns, top.name))
                t = top.end_ns

    for s in sorted(spans, key=lambda s: (s.start_ns, -s.end_ns)):
        close(s.start_ns)
        if stack and s.start_ns > t:
            out.append((t, s.start_ns, stack[-1].name))
        t = s.start_ns
        stack.append(s)
    close(float("inf"))
    return out


def idle_by_span(trace: tr.Trace, spans: List[tr.Event], t0: float,
                 t1: float) -> Dict[str, float]:
    """Idle device seconds of [t0, t1] (mean over devices) by the
    innermost span open during each gap; ``none`` where no span is."""
    pieces = innermost(spans)          # sorted and disjoint, as gaps are
    out: Dict[str, float] = {}
    n = max(1, trace.n_devices)
    for dev in trace.device_ops:
        j = 0
        for g0, g1 in tr.gaps(trace.busy(dev), t0, t1):
            while j < len(pieces) and pieces[j][1] <= g0:
                j += 1
            labelled = 0.0
            k = j
            while k < len(pieces) and pieces[k][0] < g1:
                p0, p1, name = pieces[k]
                ov = min(g1, p1) - max(g0, p0)
                out[name] = out.get(name, 0.0) + ov / 1e9 / n
                labelled += ov
                k += 1
            out["none"] = out.get("none", 0.0) + (g1 - g0 - labelled) / 1e9 / n
    return out


def main(argv: List[str]) -> int:
    if not argv or len(argv) > 2:
        print(__doc__, file=sys.stderr)
        return 2
    trace, prog = tr.load(argv[0]), load(argv[0])
    window = trace.spans_named("bench.window")
    if window:
        t0, t1 = window[0].start_ns, window[0].end_ns
    elif prog.spans:
        t0, t1 = prog.spans[0].start_ns, max(s.end_ns for s in prog.spans)
    else:
        print("no repro.* spans in the trace", file=sys.stderr)
        return 1
    inside = [s for s in prog.spans if t0 <= s.start_ns <= t1]
    waves = max(1, sum(1 for s in inside if s.name == "repro.wave"))
    print(f"window {(t1 - t0) / 1e9:.3f} s, "
          f"{sum(1 for s in inside if s.name == 'repro.wave')} waves")
    by_name: Dict[str, float] = {}
    for s in inside:
        by_name[s.name] = by_name.get(s.name, 0.0) + s.dur_ns
    print("span ms per wave:")
    for name, ns in sorted(by_name.items()):
        print(f"  {name:28s} {ns / waves / 1e6:10.3f}")
    print("idle device s by innermost span:")
    idle = idle_by_span(trace, inside, t0, t1)
    for name, s in sorted(idle.items(), key=lambda kv: -kv[1]):
        print(f"  {name:28s} {s:10.4f}")
    if len(argv) == 2:
        with open(argv[1]) as f:
            hlo = parse_hlo(f.read())
        ops = program_ops(trace, prog, hlo, t0, t1)
        total = sum(e.dur_ns for e, _ in ops)
        mapped = sum(e.dur_ns for e, op in ops if op is not None)
        gather = sum(e.dur_ns for e, op in ops if op and under_scope(op))
        print(f"device op time mapped to the HLO text: "
              f"{100 * mapped / max(total, 1):.2f}%")
        print(f"gather device ms per wave: {gather / waves / 1e6:.3f}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
