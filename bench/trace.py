"""Profiler trace -> device busy intervals, device events by name, and the
benchmark's host spans, all on the profiler's clock.

A trace is the ``*.xplane.pb`` that ``jax.profiler.start_trace`` writes
under ``<dir>/plugins/profile/<time>/``.  Device planes are named
``/device:TPU:<n>``; their ``XLA Ops`` line holds one event per executed
operation.  The benchmark's own spans are ``TraceAnnotation`` events
named ``bench.*`` on the host plane ``/host:CPU``.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Tuple

import numpy as np

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
_OP = re.compile(r"^(%[\w.-]+) = .*?\s([a-z][\w-]*)\(")


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    @property
    def op(self) -> str:
        """A device op's HLO instruction and opcode: TPU op events are
        named by their whole HLO text (``%shuffle_gemm.11 = f32[...]
        custom-call(...), custom_call_target=...``); this keeps
        ``%shuffle_gemm.11 custom-call``."""
        m = _OP.match(self.name)
        return f"{m.group(1)} {m.group(2)}" if m else self.name[:80]

    def is_instruction(self, prefix: str) -> bool:
        """True when the op's HLO instruction is named ``%<prefix>...``
        (a kernel's calls, not the ops that consume their results)."""
        return self.name.startswith("%" + prefix)


@dataclasses.dataclass
class Trace:
    device_ops: Dict[str, List[Event]]      # device plane -> op events
    spans: List[Event]                      # bench.* host spans

    @property
    def n_devices(self) -> int:
        return len(self.device_ops)

    def busy(self, device: str) -> np.ndarray:
        """Merged (start, end) intervals in which an op ran on ``device``."""
        return merge([(e.start_ns, e.end_ns) for e in self.device_ops[device]])

    def ops(self) -> List[Event]:
        return [e for evs in self.device_ops.values() for e in evs]

    def spans_named(self, name: str) -> List[Event]:
        return [s for s in self.spans if s.name == name]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _event(e) -> Event:
    return Event(e.name, float(e.start_ns), float(e.duration_ns))


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` file, or the newest one under a trace
    directory."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    data = ProfileData.from_file(path)
    device_ops: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            evs = device_ops.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs.extend(_event(e) for e in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans.extend(_event(e) for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    spans.sort(key=lambda s: s.start_ns)
    return Trace(device_ops, spans)


def merge(intervals) -> np.ndarray:
    """Union of (start, end) intervals as sorted disjoint rows."""
    if not len(intervals):
        return np.zeros((0, 2))
    iv = np.asarray(sorted(intervals), np.float64)
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out)


def covered(merged: np.ndarray, t0: float, t1: float) -> float:
    """Length of [t0, t1] covered by merged intervals."""
    if not len(merged):
        return 0.0
    lo = np.clip(merged[:, 0], t0, t1)
    hi = np.clip(merged[:, 1], t0, t1)
    return float(np.sum(hi - lo))


def gaps(merged: np.ndarray, t0: float, t1: float) -> List[Tuple[float, float]]:
    """The idle intervals of [t0, t1] between merged busy intervals."""
    out, cur = [], t0
    for s, e in merged:
        if e <= t0 or s >= t1:
            continue
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < t1:
        out.append((cur, t1))
    return out


def label_gaps(gap_list, spans: List[Event]) -> List[str]:
    """For each idle gap, the benchmark span (other than the window)
    that overlaps it most, else ``"none"``.  The spans inside the
    window are flat, so only the few that start just before a gap's end
    can overlap it."""
    flat = [s for s in spans if s.name != "bench.window"]
    starts = np.array([s.start_ns for s in flat])
    out = []
    for g0, g1 in gap_list:
        i = int(np.searchsorted(starts, g1))
        best, best_ov = "none", 0.0
        for s in flat[max(0, i - 8):i]:
            ov = min(g1, s.end_ns) - max(g0, s.start_ns)
            if ov > best_ov:
                best, best_ov = s.name, ov
        out.append(best)
    return out


def window(trace: Trace) -> Tuple[float, float]:
    """The measured window on the trace's clock: the ``bench.window``
    span."""
    w = trace.spans_named("bench.window")
    if not w:
        raise ValueError("the trace holds no bench.window span")
    return w[0].start_ns, w[0].end_ns


def summary(trace: Trace, top: int = 10) -> Dict[str, object]:
    """Busy and window seconds (mean over devices), and the breakdown:
    the device ops with the most time, and idle time by the benchmark
    span the host was in."""
    t0, t1 = window(trace)
    busy, idle_by = [], {}
    for dev in trace.device_ops:
        merged = trace.busy(dev)
        busy.append(covered(merged, t0, t1))
        idle = gaps(merged, t0, t1)
        for g, lab in zip(idle, label_gaps(idle, trace.spans)):
            idle_by[lab] = idle_by.get(lab, 0.0) + (g[1] - g[0])
    by_op: Dict[str, float] = {}
    for e in trace.ops():
        if e.start_ns >= t0 and e.end_ns <= t1:
            by_op[e.op] = by_op.get(e.op, 0.0) + e.dur_ns
    n = max(1, trace.n_devices)
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(idle_by.items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": float(np.mean(busy)) / 1e9 if busy else 0.0,
        "window_s": (t1 - t0) / 1e9,
        "breakdown": {
            "device_ops": [[k, v / 1e9] for k, v in ops],
            "idle_gaps": [[k, v / 1e9 / n] for k, v in idle],
        },
    }
