"""SigTrace observability: spans, metrics and the serving report.

Three pieces (see ``docs/observability.md``):

  * :mod:`repro.obs.trace`   — Chrome Trace Event recorder (spans,
    instants, counter tracks; one pid/tid lane per component), exported
    as ``chrome://tracing`` / Perfetto-loadable JSON;
  * :mod:`repro.obs.metrics` — process-wide counters / gauges /
    p50-p95-p99 histograms fed by hooks in the serving, streaming and
    backend layers;
  * :mod:`repro.obs.report`  — the post-run latency / occupancy /
    cache-hit-rate summary built from those metrics.

**One span call, two sinks.**  Every timed block in the program is

    with obs.span("SignalService", "wave.stack", wave=n) as sp:
        ...
        sp.set(pad_waste=w)          # values known only at exit

While a ``jax.profiler`` session records, the span is a
``TraceAnnotation`` named ``repro.<name>`` in the profiler's own trace,
so it lies on the same clock as the device ops.  While :data:`ENABLED`
is set, it is also an ``X`` event of the SigTrace Chrome JSON.  With
neither on, :func:`span` returns one shared no-op context: no
allocation, no clock reading.  Span args are ints or strings.

Counters, gauges, histograms and instants are fed only while
:data:`ENABLED` is set (``if obs.ENABLED:`` at each site).
:func:`enable` / :func:`disable` flip it; :func:`enable_from_env`
honors ``REPRO_TRACE`` (``1``/``true`` to enable, any other non-empty
value is used as the trace-export path).  Instrumentation never changes
computed arrays: hooks record host-side numbers only, outside the jitted
programs.
"""

from __future__ import annotations

import os
import time
from typing import Optional

from jax.profiler import TraceAnnotation

from .trace import (Tracer, get_tracer, reset_tracer, validate_trace,
                    TraceError)
from .metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                      get_registry, reset_registry)
from .report import REPORT_SCHEMA_VERSION, build_report, render_report

__all__ = ["ENABLED", "enable", "disable", "enabled", "enable_from_env",
           "reset", "now", "tracer", "metrics",
           "instant", "span", "NO_SPAN",
           "Tracer", "get_tracer", "reset_tracer", "validate_trace",
           "TraceError", "Counter", "Gauge", "Histogram",
           "MetricsRegistry", "get_registry", "reset_registry",
           "REPORT_SCHEMA_VERSION", "build_report", "render_report",
           "default_trace_path"]

# THE hot-path switch: instrumentation sites read this module attribute
# and branch — nothing below runs while it is False.
ENABLED = False

_DEFAULT_TRACE_PATH = "artifacts/repro_trace.json"
_trace_path: Optional[str] = None


def enable(trace_path: Optional[str] = None) -> None:
    """Turn instrumentation on.  ``trace_path`` (optional) is where
    :func:`default_trace_path` / bench shutdown hooks export the trace."""
    global ENABLED, _trace_path
    get_tracer()            # anchor the trace clock before the first hook
    ENABLED = True
    if trace_path is not None:
        _trace_path = trace_path


def disable() -> None:
    global ENABLED
    ENABLED = False


def enabled() -> bool:
    return ENABLED


def reset() -> None:
    """Disable AND drop all recorded state (fresh tracer + registry)."""
    disable()
    reset_tracer()
    reset_registry()


def enable_from_env(env: str = "REPRO_TRACE") -> bool:
    """Enable instrumentation when ``$REPRO_TRACE`` is set: ``1`` /
    ``true`` / ``yes`` enable with the default export path; ``0`` /
    ``false`` / empty leave it off; anything else is taken as the
    export path.  Returns whether instrumentation is now enabled."""
    val = os.environ.get(env, "").strip()
    if not val or val.lower() in ("0", "false", "no"):
        return ENABLED
    if val.lower() in ("1", "true", "yes"):
        enable()
    else:
        enable(trace_path=val)
    return True


def default_trace_path() -> str:
    """Where to export the trace: the ``enable()`` argument, the
    ``REPRO_TRACE`` path, or ``artifacts/repro_trace.json``."""
    return _trace_path or _DEFAULT_TRACE_PATH


# -- hook helpers ----------------------------------------------------------

now = time.perf_counter_ns

# True while a jax.profiler session records (one C++ call, no allocation)
_profiling = TraceAnnotation.is_enabled


def tracer() -> Tracer:
    return get_tracer()


def metrics() -> MetricsRegistry:
    return get_registry()


def instant(lane: str, name: str, **args) -> None:
    """Record an ``i`` event (call ONLY under ``if obs.ENABLED:``)."""
    get_tracer().instant(lane, name, args or None)


class _NoSpan:
    """The span while neither sink is on: enter, set and exit do nothing,
    and it is false, so a site can skip computing args for it
    (``if sp: sp.set(...)``)."""

    __slots__ = ()

    def __bool__(self):
        return False

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args) -> None:
        pass


NO_SPAN = _NoSpan()


class _Span:
    """One span into the sinks that were on when it was made: the
    profiler's ``TraceAnnotation`` and/or the SigTrace ``X`` event."""

    __slots__ = ("lane", "name", "args", "_annotation", "_record", "_t0")

    def __init__(self, lane, name, args, profiling, record):
        self.lane, self.name, self.args = lane, name, args
        self._annotation = (TraceAnnotation("repro." + name, **args)
                            if profiling else None)
        self._record = record
        self._t0 = 0

    def __enter__(self):
        if self._annotation is not None:
            self._annotation.__enter__()
        if self._record:
            self._t0 = time.perf_counter_ns()
        return self

    def set(self, **args) -> None:
        """Add args known only inside the span (bytes, pad waste)."""
        if self._annotation is not None:
            self._annotation.set_metadata(**args)
        self.args.update(args)

    def __exit__(self, *exc):
        if self._record:
            get_tracer().complete(self.lane, self.name, self._t0,
                                  self.args or None)
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        return False


def span(lane: str, name: str, **args):
    """Context manager timing a block: ``repro.<name>`` in the profiler's
    trace while a profiler session records, an ``X`` event named
    ``name`` on ``lane`` of the SigTrace JSON while :data:`ENABLED`;
    :data:`NO_SPAN` while neither is on."""
    profiling = _profiling()
    if not (profiling or ENABLED):
        return NO_SPAN
    return _Span(lane, name, args, profiling, ENABLED)
