"""Per-layer metric readers.  Each ``<name>.py`` holds ``read(run)``,
which reduces one traced run (:class:`RunData`) to one number, or
returns None where it finds nothing to read (the harness then leaves
the metric out of the result line).
"""

from __future__ import annotations

import dataclasses

from bench import trace as tr


@dataclasses.dataclass
class RunData:
    cfg: dict
    mod: object                 # the configuration's module (``flops``)
    record: object              # drive.Record of the run
    trace: tr.Trace
    peaks: dict                 # bench.peaks entry of the device


def host_ms_per_span(run: RunData, names, per: str):
    """Mean host-only milliseconds per ``per`` span: the summed length
    of the ``names`` spans, less the device busy time inside them, over
    the number of ``per`` spans in the window."""
    t0, t1 = tr.window(run.trace)
    spans = [s for s in run.trace.spans if s.name in names
             and t0 <= s.start_ns <= t1]
    n = sum(1 for s in spans if s.name == per)
    if not n or not run.trace.device_ops:
        return None
    dev = next(iter(run.trace.device_ops))
    merged = run.trace.busy(dev)
    host = sum(s.dur_ns - tr.covered(merged, s.start_ns, s.end_ns)
               for s in spans)
    return host / n / 1e6
