"""Warm-up, measured window and drain of each traffic mode.

Each mode warms every shape its window can produce, then drives the
service from the client's side for the window, and returns a
:class:`Record`: the end-to-end metrics it measured on the host clock,
the answers to check against the reference, and what the per-layer
readers reduce.  Spans (``bench.*``) are written into the profiler's
trace only in a traced run.
"""

from __future__ import annotations

import contextlib
import dataclasses
import resource
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import arith, generator

DRAIN_S = 60.0          # how long past the close a due answer is awaited
WARM_BACKLOG = 6        # chunks a warmed stream session may fall behind


def now() -> float:
    return time.perf_counter()


def sleep_until(t: float) -> None:
    """Sleep to within half a millisecond of ``t``, then spin."""
    dt = t - now()
    if dt > 1e-3:
        time.sleep(dt - 5e-4)
    while now() < t:
        pass


@dataclasses.dataclass
class Record:
    attempted: int = 0
    failed: int = 0
    e2e: Dict[str, float] = dataclasses.field(default_factory=dict)
    # (label, input samples, served outputs) to check against the reference
    answers: List[tuple] = dataclasses.field(default_factory=list)
    # one dict per executed wave: t0, t1 (host clock), bucket, lens
    waves: List[dict] = dataclasses.field(default_factory=list)
    ticks: int = 0
    window_s: float = 0.0
    notes: Dict[str, object] = dataclasses.field(default_factory=dict)


class CompileCounter:
    """Counts XLA compiles, cache loads and traces through
    ``jax.monitoring`` while ``on``."""

    EVENTS = {"/jax/core/compile/backend_compile_duration": "compiles",
              "/jax/compilation_cache/cache_retrieval_time_sec": "cache_loads",
              "/jax/core/compile/jaxpr_trace_duration": "traces"}

    def __init__(self):
        import jax
        self.on = False
        self.counts = {v: 0 for v in self.EVENTS.values()}
        jax.monitoring.register_event_duration_secs_listener(self._hear)

    def _hear(self, event, duration, **_):
        if self.on and event in self.EVENTS:
            self.counts[self.EVENTS[event]] += 1


@dataclasses.dataclass
class Cell:
    """What a mode needs: the service under test, its graph's name, the
    configuration, the mix and the run's settings."""
    service: object
    name: str
    cfg: dict
    mod: object
    traffic: dict
    seed: int
    seconds: float
    t_process: float
    trace: bool
    start_trace: Callable[[], None]
    stop_trace: Callable[[], None]
    counter: CompileCounter

    def span(self, name: str, **kw):
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name, **kw)

    @contextlib.contextmanager
    def window(self, rec: Record):
        """The measured window: set-up ends, compile counting and the
        trace start; yields the window's start on the host clock."""
        self.start_trace()
        t0 = now()
        rec.e2e["setup_s"] = t0 - self.t_process
        self.counter.counts = dict.fromkeys(self.counter.counts, 0)
        self.counter.on = True
        use0 = resource.getrusage(resource.RUSAGE_SELF)
        with self.span("bench.window"):
            yield t0
            rec.window_s = now() - t0
        use1 = resource.getrusage(resource.RUSAGE_SELF)
        self.counter.on = False
        self.stop_trace()
        rec.notes.update({f"{k}_in_window": v
                          for k, v in self.counter.counts.items()})
        # whether the host held the window back: the process's CPU time,
        # and how often the kernel took a core from it
        rec.notes["host_cpu_s_in_window"] = (
            use1.ru_utime - use0.ru_utime + use1.ru_stime - use0.ru_stime)
        rec.notes["involuntary_switches_in_window"] = (
            use1.ru_nivcsw - use0.ru_nivcsw)


def _request(name, rid, samples):
    from repro.serving import SignalRequest
    return SignalRequest(rid=rid, graph=name, samples=samples)


def _sample(seed, keys, n, always=()):
    """``n`` of ``keys`` drawn from the seed, plus ``always``."""
    keys = list(keys)
    pick = arith.np_rng(seed, 5).permutation(len(keys))[:n]
    return sorted({keys[i] for i in pick} | set(always))


def _drain(svc) -> None:
    """Run warm-up waves until the queue is empty (a queue of ``k``
    requests of one bucket is one wave of ``k`` rows)."""
    while svc.pending():
        svc.step()


def _buckets_for(buckets, lo, hi):
    """The pinned buckets a length in [lo, hi] can land in."""
    first = next(b for b in buckets if b >= lo)
    last = next(b for b in buckets if b >= hi)
    return [b for b in buckets if first <= b <= last]


def open_loop(cell: Cell) -> Record:
    """One-shot requests due at fixed times; each timed from when it was
    due to when ``step`` returned it."""
    svc, name, tr = cell.service, cell.name, cell.traffic
    pool = generator.audio_pool(cell.mod, cell.cfg, tr, cell.seed)
    reqs = generator.open_loop(tr, cell.seed, cell.seconds, len(pool))
    buckets = cell.cfg["service"]["buckets"]
    lo, hi = tr["length"]["min"], tr["length"]["max"]
    # warm every (bucket, rows) program the window can produce
    for b in _buckets_for(buckets, lo, hi):
        n = min(b, hi)
        for k in range(1, svc.batch_size + 1):
            for j in range(k):
                svc.submit(_request(name, -1 - j, pool[:n]))
            _drain(svc)

    rec = Record(attempted=len(reqs))
    done: Dict[int, float] = {}
    results: Dict[int, dict] = {}
    late: List[float] = []
    by_rid = {r.idx: r for r in reqs}

    def submit(r, t):
        svc.submit(_request(name, r.idx, pool[r.offset:r.offset + r.length]))
        late.append(t - r.due)

    def step(t0):
        with cell.span("bench.step"):
            ts = now()
            res = svc.step()
            te = now()
        rec.waves.append({"t0": ts, "t1": te, "lens": [
            by_rid[rid].length for rid in res],
            "bucket": next(b for b in buckets
                           if b >= max(by_rid[rid].length for rid in res))}
            if res else {"t0": ts, "t1": te, "lens": [], "bucket": 0})
        for rid, out in res.items():
            done[rid] = te - t0
            results[rid] = out

    i = 0
    with cell.window(rec) as t0:
        while True:
            t = now() - t0
            if t >= cell.seconds:
                break
            while i < len(reqs) and reqs[i].due <= t:
                submit(reqs[i], t)
                i += 1
            if svc.pending():
                step(t0)
            else:
                nxt = reqs[i].due if i < len(reqs) else cell.seconds
                with cell.span("bench.wait"):
                    sleep_until(t0 + min(nxt, cell.seconds))
        n_window_waves = len(rec.waves)
        rec.notes["backlog_at_close"] = svc.pending() + len(reqs) - i
    # requests due in the window are awaited past its close
    for r in reqs[i:]:
        submit(r, now() - t0)
    while svc.pending() and now() - t0 < cell.seconds + DRAIN_S:
        step(t0)
    rec.waves = rec.waves[:n_window_waves]
    lat = np.array([done[r.idx] - r.due for r in reqs if r.idx in done])
    rec.failed = len(reqs) - len(done)
    if len(lat):
        rec.e2e["latency_p50_ms"] = float(np.percentile(lat, 50)) * 1e3
        rec.e2e["latency_p95_ms"] = float(np.percentile(lat, 95)) * 1e3
    half = [np.median([done[r.idx] - r.due for r in reqs[a:b]
                       if r.idx in done] or [np.nan]) * 1e3
            for a, b in ((0, len(reqs) // 2), (len(reqs) // 2, len(reqs)))]
    rec.notes["latency_p50_ms_by_half"] = half
    rec.notes["generator_late_ms_p95"] = float(np.percentile(late, 95)) * 1e3
    rec.notes["generator_late_ms_max"] = float(np.max(late)) * 1e3
    longest = max(done, key=lambda rid: by_rid[rid].length, default=None)
    for rid in _sample(cell.seed, done, tr["check_sample"],
                       [] if longest is None else [longest]):
        r = by_rid[rid]
        rec.answers.append((f"request {rid}",
                            pool[r.offset:r.offset + r.length], results[rid]))
    return rec


def backlog(cell: Cell) -> Record:
    """An offline queue kept ``min_waves`` waves deep; counts the audio
    whose scores were delivered inside the window."""
    svc, name, tr = cell.service, cell.name, cell.traffic
    pool = generator.audio_pool(cell.mod, cell.cfg, tr, cell.seed)
    clips = generator.Backlog(tr, cell.seed, len(pool))
    depth = tr["min_waves"] * svc.batch_size
    bucket = next(b for b in cell.cfg["service"]["buckets"]
                  if b >= clips.length)
    for _ in range(2):
        for j in range(svc.batch_size):
            svc.submit(_request(name, -1 - j, pool[:clips.length]))
        _drain(svc)

    rec = Record()
    by_rid = {}
    results: Dict[int, dict] = {}
    audio_s = 0.0
    answered = 0
    with cell.window(rec) as t0:
        while now() - t0 < cell.seconds:
            while svc.pending() < depth:
                c = clips.take()
                by_rid[c.idx] = c
                svc.submit(_request(name, c.idx,
                                    pool[c.offset:c.offset + c.length]))
            with cell.span("bench.step"):
                ts = now()
                res = svc.step()
                te = now()
            rec.waves.append({"t0": ts, "t1": te, "bucket": bucket,
                              "lens": [clips.length] * len(res)})
            answered += len(res)
            if te - t0 <= cell.seconds:
                results.update(res)
                audio_s += len(res) * clips.length / cell.cfg["sample_rate"]
    # a clip that left the queue without an answer never comes
    rec.failed = clips.next - answered - svc.pending()
    rec.attempted = len(results) + rec.failed
    rec.e2e["audio_throughput"] = audio_s / cell.seconds
    rec.notes["clips_delivered"] = len(results)
    # a slow run: a few long stalls, or every wave slower
    ms = np.array([w["t1"] - w["t0"] for w in rec.waves]) * 1e3
    if len(ms):
        med = float(np.median(ms))
        slow = ms[ms > 1.5 * med]
        rec.notes["wave_ms"] = {
            "p50": med, "p95": float(np.percentile(ms, 95)),
            "max": float(ms.max()), "over_1.5x_p50": int(len(slow)),
            "over_1.5x_p50_s": float(slow.sum()) / 1e3,
            "outside_step_s": rec.window_s - float(ms.sum()) / 1e3}
    for rid in _sample(cell.seed, results, tr["check_sample"]):
        c = by_rid[rid]
        rec.answers.append((f"clip {rid}", pool[c.offset:c.offset + c.length],
                            results[rid]))
    return rec


def _concat(mod, pieces: List[dict]) -> dict:
    out = {}
    for k, axis in mod.STREAM_AXES.items():
        got = [np.asarray(p[k]) for p in pieces if k in p]
        if got:
            out[k] = np.concatenate(got, axis=axis)
    return out


def _warm_streams(cell: Cell, n_sessions: int, audio: np.ndarray) -> None:
    """Every stack width 1..n with the block, ring-buffer and tail shapes
    a session meets when it keeps up and when it falls up to
    ``WARM_BACKLOG`` chunks behind: ``k`` sessions fed ``b`` chunks one
    by one, then ticked until nothing is ready, a few times over, then
    closed."""
    svc, chunk = cell.service, cell.traffic["chunk"]
    for burst in range(1, WARM_BACKLOG + 1):
        for k in range(1, n_sessions + 1):
            sessions = [svc.open_stream(cell.name) for _ in range(k)]
            pos = 0
            for _ in range(6 if burst == 1 else 3):
                for _ in range(burst):
                    for s in sessions:
                        s.feed(audio[pos:pos + chunk])
                    pos += chunk
                while True:
                    svc.stream_step()
                    for s in sessions:
                        s.read()
                    if not svc.stream_pending():
                        break
            for s in sessions:
                s.close()


def streams(cell: Cell) -> Record:
    """Real-time sessions fed on the wall clock; the lag of each piece
    read is its read time minus the due time of the newest chunk fed
    before the tick that produced it."""
    svc, tr, mod = cell.service, cell.traffic, cell.mod
    sess = generator.streams(mod, cell.cfg, tr, cell.seed, cell.seconds)
    chunk, period = tr["chunk"], tr["period_s"]
    _warm_streams(cell, len(sess), sess[0].audio)

    live = [svc.open_stream(cell.name) for _ in sess]
    fed = [0] * len(sess)
    newest: List[Optional[float]] = [None] * len(sess)
    pieces: List[List[dict]] = [[] for _ in sess]
    lags: List[float] = []
    lag_at: List[float] = []
    late: List[float] = []
    rec = Record(attempted=len(sess))
    with cell.window(rec) as t0:
        while True:
            t = now() - t0
            if t >= cell.seconds:
                break
            with cell.span("bench.feed"):
                for k, s in enumerate(sess):
                    while s.phase + fed[k] * period <= t \
                            and (fed[k] + 1) * chunk <= len(s.audio):
                        live[k].feed(s.audio[fed[k] * chunk:
                                             (fed[k] + 1) * chunk])
                        newest[k] = s.phase + fed[k] * period
                        late.append(t - newest[k])
                        fed[k] += 1
            due = list(newest)
            with cell.span("bench.stream_step"):
                svc.stream_step()
            rec.ticks += 1
            with cell.span("bench.read"):
                for k, s in enumerate(live):
                    out = s.read()
                    if out and any(np.size(v) for v in out.values()):
                        pieces[k].append(out)
                        if due[k] is not None:
                            lags.append(now() - t0 - due[k])
                            lag_at.append(due[k])
            if not svc.stream_pending():
                nxt = min(s.phase + fed[k] * period
                          for k, s in enumerate(sess))
                with cell.span("bench.wait"):
                    sleep_until(t0 + min(nxt, cell.seconds))
    for k, s in enumerate(live):
        pieces[k].append(s.close())
    rec.e2e["stream_lag_p95_ms"] = float(np.percentile(lags, 95)) * 1e3
    rec.notes["pieces_in_window"] = len(lags)
    rec.notes["stream_lag_ms_max"] = float(np.max(lags)) * 1e3
    first = np.asarray(lag_at) < cell.seconds / 2
    rec.notes["stream_lag_p50_ms_by_half"] = [
        float(np.median(np.asarray(lags)[m])) * 1e3 if m.any() else None
        for m in (first, ~first)]
    rec.notes["ticks_in_window"] = rec.ticks
    rec.notes["feed_late_ms_p95"] = float(np.percentile(late, 95)) * 1e3
    rec.notes["feed_late_ms_max"] = float(np.max(late)) * 1e3
    for k in _sample(cell.seed, range(len(sess)), tr["check_sessions"]):
        rec.answers.append((f"session {k}", sess[k].audio[:fed[k] * chunk],
                            _concat(mod, pieces[k])))
    return rec


MODES = {"open_loop": open_loop, "backlog": backlog, "streams": streams}
