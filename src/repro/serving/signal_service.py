"""Signal-graph serving: continuous-batched DSP requests co-scheduled
with LLM decode.

The paper's system-level story is ONE array serving both DL and DSP work
concurrently (Fig 9 runs an FFT->CNN->iFFT pipeline while the same DLA
keeps its deep-learning duties).  This module is the serving counterpart:

  * :class:`SignalService` — registry of named :class:`SignalGraph`
    pipelines with a continuous-batching request loop.  Mixed-length
    requests are padded up to a small set of compile-cached **bucket**
    lengths (powers of two, or config-supplied) and batched per
    ``(graph, bucket)``; per-request valid-length masks are threaded
    through the compiled graph (:meth:`CompiledSignalGraph.masked_jit`)
    so padded results equal unpadded execution — bit-identical for the
    FFT/IIR/pointwise stage classes, float32-ULP-close for FIR im2col
    GEMMs whose XLA lowering is row-count dependent (the streaming
    runtime's caveat, tests/test_signal_bucketing.py).  New
    requests join the next tick's batch mid-flight — the wave is
    re-formed from the live queue every step, like token-level
    continuous batching in :mod:`repro.serving.engine`.
  * :class:`StreamSession` — a per-connection streaming handle
    (:meth:`SignalService.open_stream`): chunked submissions accumulate
    in per-connection :class:`~repro.signal.streaming.StreamState`
    pytrees, and every :meth:`SignalService.stream_step` stacks the
    ready blocks of same-graph sessions into ONE jitted core call.

Both paths carry the SigProgram multi-output contract: graphs declared
with ``outputs()``/``tap()`` return per-output dicts from
:meth:`SignalService.step`/``serve`` (each output trimmed back to the
request's true length along its own frames/time axis) and from
:meth:`StreamSession.read`/``close`` (frame taps emitted per block) —
one compiled core program per graph, no second registration for a
monitoring tap.
  * :class:`CoScheduler` — drives a :class:`~repro.serving.engine.
    ServingEngine` and a :class:`SignalService` on one step loop, with a
    pluggable :class:`SchedulePolicy` deciding what runs each tick:
    ``round_robin`` (one decode step + one DSP batch per tick, the
    original behaviour), ``latency_aware`` (earliest-deadline-first
    across both workload classes), or ``cost_balanced`` (uses
    :func:`repro.core.perf_model.step_cost_estimate` /
    ``decode_step_cost`` to keep the DSP/DL array-occupancy split near a
    target — the paper's §V utilization argument).

Greedy-decode results are identical to ``ServingEngine.serve`` and DSP
results identical to offline graph execution (tests/test_signal_service.py,
tests/test_signal_bucketing.py).
"""

from __future__ import annotations

import bisect
import dataclasses
import math
from typing import Any, Callable, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..signal.graph import CompiledSignalGraph, FuseLevel, SignalGraph
from ..signal.streaming import (StreamState, StreamStructure, commit_frames,
                                drain_state, finalize_piece, push_chunk,
                                ready_spec, restore_state, snapshot_state,
                                take_block, tap_rows)
from .engine import DecodeWave, Request, ServingEngine
from .scheduler import SigSched
from .signal_mesh import DeviceRouter, SignalMesh

__all__ = ["SignalRequest", "SignalService", "StreamSession", "CoScheduler",
           "SchedulePolicy", "RoundRobinPolicy", "LatencyAwarePolicy",
           "CostBalancedPolicy", "get_policy", "TickPlan",
           "SignalMesh", "DeviceRouter", "SigSched"]


def _params_equal(a, b) -> bool:
    """True when two params pytrees are interchangeable for execution:
    same structure, equal leaves (exact array equality — scheduling must
    never change results, so 'close enough' is not equal)."""
    if a is b:
        return True
    ta = jax.tree_util.tree_structure(a)
    tb = jax.tree_util.tree_structure(b)
    if ta != tb:
        return False
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        if x.shape != y.shape or x.dtype != y.dtype \
                or not np.array_equal(x, y):
            return False
    return True


def _to_host(out):
    """Device results -> numpy, preserving the per-output dict of
    multi-output SigPrograms."""
    return jax.tree_util.tree_map(np.asarray, out)


def _ckpt_encode(obj, _leaves=None):
    """Split a :meth:`SignalService.checkpoint` tree into a JSON-able
    structure encoding plus a flat list of array leaves (what
    :class:`~repro.checkpoint.Checkpointer` stores as ``leaf_*.npy``).
    Handles the snapshot vocabulary: dicts (string-or-None keys), lists,
    tuples, :class:`StreamState` pytrees, arrays, and JSON scalars.
    Returns ``(encoding, leaves)``; inverse is :func:`_ckpt_decode`."""
    top = _leaves is None
    leaves = [] if top else _leaves
    if isinstance(obj, StreamState):
        enc = {"__k__": "state",
               "pre": _ckpt_encode(list(obj.pre), leaves),
               "post": _ckpt_encode(list(obj.post), leaves),
               "buf": _ckpt_encode(obj.buf, leaves),
               "tail": _ckpt_encode(obj.tail, leaves),
               "counters": [int(obj.buf_start), int(obj.total),
                            int(obj.f_next), int(obj.emitted),
                            [int(d) for d in obj.batch_shape]]}
    elif isinstance(obj, (np.ndarray, jax.Array)):
        leaves.append(np.asarray(obj))
        enc = {"__k__": "leaf", "i": len(leaves) - 1}
    elif isinstance(obj, dict):
        enc = {"__k__": "dict",
               "items": [[k, _ckpt_encode(v, leaves)]
                         for k, v in obj.items()]}
    elif isinstance(obj, (list, tuple)):
        enc = {"__k__": "list" if isinstance(obj, list) else "tuple",
               "items": [_ckpt_encode(v, leaves) for v in obj]}
    elif isinstance(obj, np.integer):
        enc = int(obj)
    elif isinstance(obj, np.floating):
        enc = float(obj)
    else:
        enc = obj                       # int / float / str / bool / None
    return (enc, leaves) if top else enc


def _ckpt_decode(enc, leaves):
    """Inverse of :func:`_ckpt_encode`."""
    if isinstance(enc, dict) and "__k__" in enc:
        k = enc["__k__"]
        if k == "leaf":
            return np.asarray(leaves[enc["i"]])
        if k == "dict":
            return {kk: _ckpt_decode(v, leaves)
                    for kk, v in enc["items"]}
        if k == "list":
            return [_ckpt_decode(v, leaves) for v in enc["items"]]
        if k == "tuple":
            return tuple(_ckpt_decode(v, leaves) for v in enc["items"])
        if k == "state":
            c = enc["counters"]
            return StreamState(
                pre=tuple(_ckpt_decode(enc["pre"], leaves)),
                post=tuple(_ckpt_decode(enc["post"], leaves)),
                buf=_ckpt_decode(enc["buf"], leaves),
                tail=_ckpt_decode(enc["tail"], leaves),
                buf_start=c[0], total=c[1], f_next=c[2], emitted=c[3],
                batch_shape=tuple(c[4]))
        raise ValueError(f"unknown checkpoint node kind {k!r}")
    return enc


@dataclasses.dataclass
class SignalRequest:
    rid: int
    graph: str
    samples: np.ndarray            # (T,) one channel of signal
    deadline: float = math.inf     # scheduler hint (latency_aware policy)
    done: bool = False
    error: Optional[str] = None    # set when the service drops the request
    seq: int = -1                  # arrival order (assigned by submit)


@dataclasses.dataclass
class _Registration:
    graph: SignalGraph
    params: object
    struct: Optional[StreamStructure]   # None => not bucketable/streamable


@dataclasses.dataclass(frozen=True)
class GroupInfo:
    """One pending batch group: requests sharing a (graph, length-bucket)
    compiled program."""
    key: Tuple[str, int]
    count: int
    oldest_seq: int
    earliest_deadline: float


class SignalService:
    """Continuous-batched serving of registered signal graphs.

    Compiled callables are cached per ``(graph, bucket)`` — requests of
    any length up to a bucket share that bucket's XLA program, padded
    and masked back to the unpadded results (bitwise, except FIR im2col
    GEMMs which match to float32 ULPs).  ``buckets`` optionally pins
    the admissible lengths (sorted ascending); the default is powers of
    two.  Graphs whose math is not local in time (a ``dct``/``fft``/
    ``dwt`` over the raw input axis) cannot be masked and fall back to
    exact-length grouping; ``bucketing=False`` forces that for all
    graphs.

    ``backend`` selects the execution backend for every compiled
    program the service runs — bucket compiles AND streaming-session
    cores (:mod:`repro.signal.backends`: ``"reference"`` jnp
    interpretation, ``"pallas"`` shuffle-GEMM array kernels; same
    switch as ``SignalGraph.compile`` / ``StreamingRunner``).

    ``mesh`` shards the service data-parallel over a device mesh
    (:class:`~repro.serving.signal_mesh.SignalMesh`; an int shard
    count or a jax ``Mesh`` coerce).  Bucket batches pad their row
    count to a shard multiple, are placed row-sharded via
    ``NamedSharding`` and execute as a row-split ``shard_map``
    (:meth:`SignalMesh.row_parallel` — XLA cannot partition the
    ``pallas`` backend's kernels); streaming sessions get device
    affinity (a least-loaded shard assigned at ``open_stream``, where
    their carried :class:`StreamState` then stays put across ticks); a
    :class:`DeviceRouter` keeps the per-device cycle ledger the
    ``CoScheduler`` reports.  Outputs equal the unsharded path's up to
    float32 rounding — pad rows are zero rows of row-independent math,
    trimmed before anything reads them, and the per-device programs
    see fewer rows.  ``mesh=None`` (default) is
    the original single-device service, byte for byte.
    """

    def __init__(self, batch_size: int = 8,
                 fuse: "FuseLevel | int" = FuseLevel.STREAM,
                 buckets: Optional[List[int]] = None,
                 bucketing: bool = True,
                 block_frames: int = 8,
                 backend="reference",
                 mesh: "SignalMesh | int | None" = None,
                 precision=None,
                 scheduler: "SigSched | dict | bool | None" = None):
        from ..signal.backends import PallasBackend, get_backend
        self.batch_size = batch_size
        self.fuse = FuseLevel.coerce(fuse)
        # one execution backend per service: every bucket compile and
        # every streaming-session core call goes through it (same
        # ``backend=`` switch as SignalGraph.compile / StreamingRunner).
        self.backend = get_backend(backend)
        if precision is not None:
            # serve a calibrated program: rebuild the array backend with
            # the policy.  The policy is part of the backend's
            # ``cache_key``, so bucket compiles and streaming cores key
            # on it — calibrated serving is bit-stable with offline and
            # StreamingRunner execution under the same policy.
            if not isinstance(self.backend, PallasBackend):
                raise ValueError(
                    f"SignalService(precision=...) needs the 'pallas' "
                    f"backend (got {self.backend.name!r}); only the "
                    f"array backend int-routes calibrated widths")
            self.backend = PallasBackend(interpret=self.backend.interpret,
                                         precision=precision)
        self.precision = precision
        self.mesh = SignalMesh.coerce(mesh)
        self.router = DeviceRouter(self.mesh.n_shards) \
            if self.mesh is not None else None
        self.buckets = sorted(int(b) for b in buckets) if buckets else None
        self.bucketing = bucketing
        self.block_frames = int(block_frames)
        self._graphs: Dict[str, _Registration] = {}
        self._compiled: Dict[Tuple[str, int], CompiledSignalGraph] = {}
        self._jitted: Dict[Tuple[str, int], object] = {}
        self._masked_jitted: Dict[Tuple[str, int], object] = {}
        self._vmap_jitted: Dict[Tuple, object] = {}
        self._cost_cache: Dict[Tuple[str, int], int] = {}
        self._fp_cache: Dict[Tuple[str, int], Optional[Tuple]] = {}
        self._called: set = set()     # jitted entries called, per shape
        self._queue: List[SignalRequest] = []
        self._seq = 0
        self._wave_seq = 0            # joins a wave's spans (``wave=``)
        self._sessions: Dict[str, List["StreamSession"]] = {}
        self._sid = 0
        self._ckpt_seq = 0            # next save_checkpoint step number
        # est_cycles accumulates the perf-model cost of every executed
        # batch (one-shot + streaming); the CoScheduler reads deltas for
        # its occupancy accounting.  wall_cycles is the sharded-aware
        # virtual clock: per execution it advances by the MAX per-device
        # share (devices run concurrently), so on a mesh it runs up to
        # n_shards-fold slower than est_cycles — the latency clock the
        # mesh bench sweeps.  They coincide when mesh is None.
        self.est_cycles = 0
        self.wall_cycles = 0
        self.stats = {"compiles": 0, "batches": 0, "bucketed": 0,
                      "exact": 0, "dropped": 0, "detached_sessions": 0,
                      "core_calls": 0, "flush_core_calls": 0,
                      "stream_ticks": 0, "bucket_overflow": 0,
                      "param_splits": 0}
        # the dispatch brain: SigSched decides which wave runs each
        # step() tick (cross-graph batching, deadline-aware EDF,
        # preemptible row budgets).  Default configuration reduces to
        # the legacy FIFO pick when nothing carries a finite deadline.
        # ``scheduler=False`` disables it (the pure pre-SigSched loop);
        # a dict passes SigSched options; an instance is adopted.
        if scheduler is False:
            self.scheduler: Optional[SigSched] = None
        elif scheduler is None or scheduler is True:
            self.scheduler = SigSched(self)
        elif isinstance(scheduler, dict):
            self.scheduler = SigSched(self, **scheduler)
        else:
            scheduler.service = self
            self.scheduler = scheduler

    # -- registry -----------------------------------------------------------
    def register(self, name: str, graph: SignalGraph, params=None) -> None:
        """Register (or replace) a named graph.  Replacement drops the
        stale compile/cost caches, any queued requests referencing the
        old graph, AND detaches its open streaming sessions (their
        carried state was built under the old graph's frame/hop) — their
        ``error`` fields say why.  Nothing queued or streaming can ever
        execute against a graph it was not submitted for."""
        replacing = name in self._graphs
        try:
            struct = StreamStructure.analyze(graph)
        except ValueError:
            struct = None                     # offline-only: exact lengths
        self._graphs[name] = _Registration(graph, params, struct)
        for key in [k for k in self._compiled if k[0] == name]:
            del self._compiled[key]
            self._jitted.pop(key, None)
            self._masked_jitted.pop(key, None)
        for key in [k for k in self._vmap_jitted if k[0] == name]:
            del self._vmap_jitted[key]
        self._called = {sig for sig in self._called if sig[1] != name}
        for cache in (self._cost_cache, self._fp_cache):
            for key in [k for k in cache
                        if k[0] in (name, f"{name}//core")]:
                del cache[key]
        if replacing:
            stale = [r for r in self._queue if r.graph == name]
            for r in stale:
                self._queue.remove(r)
            if self.scheduler is not None:
                # claimed split-wave rows live outside the queue
                stale += self.scheduler.drop_graph(name)
            for r in stale:
                r.error = (f"graph {name!r} was re-registered while the "
                           f"request was queued; resubmit")
            self.stats["dropped"] += len(stale)
            for sess in self._sessions.pop(name, []):
                sess.closed = True
                sess.error = (f"graph {name!r} was re-registered; the "
                              f"stream's carried state no longer applies "
                              f"— open a new session")
                self.stats["detached_sessions"] += 1

    def compiled_for(self, name: str, length: int) -> CompiledSignalGraph:
        key = (name, length)
        if key not in self._compiled:
            with obs.span("SignalService", "compile", graph=name,
                          bucket=length, entry="graph",
                          backend=self.backend.name) as sp:
                graph = self._graphs[name].graph
                self._compiled[key] = graph.compile(
                    length, fuse=self.fuse, backend=self.backend)
                self.stats["compiles"] += 1
                if obs.ENABLED:
                    self._record_lowering(self._compiled[key], sp)
        return self._compiled[key]

    def _record_lowering(self, compiled, sp) -> None:
        """Accumulate the backend's fused-vs-emulated route counts
        (``lowering_report``) of one bucket compile into the metrics
        registry and its ``compile`` span — the runtime side of
        ``signal_graph_report``'s static pass accounting."""
        lowering = getattr(compiled, "lowering_report", None)
        if lowering is None:
            return
        rep = lowering()
        m = obs.metrics()
        pre = f"backend.{rep['name']}"
        counts = {f"fabric_{k}": n for k, n in rep["fabric_passes"].items()}
        counts.update((f"array_{route}", n)
                      for route, n in rep["array_passes"].items())
        for k, n in counts.items():
            m.counter(f"{pre}.{k}").inc(n)
        sp.set(**counts)

    def _first_call_span(self, sig: Tuple, **args):
        """The ``compile`` span around the first call of a jitted entry
        at one shape — the call that traces and compiles it — and the
        shared no-op span on every later call."""
        if sig in self._called:
            return obs.NO_SPAN
        self._called.add(sig)
        return obs.span("SignalService", "compile", **args)

    # -- length bucketing ---------------------------------------------------
    def bucket_for(self, name: str, length: int) -> Optional[int]:
        """The compile length serving a request of ``length`` samples:
        the smallest admissible bucket >= length (and >= the graph's
        minimum input), found by ``bisect`` over the sorted pinned
        buckets.  None => exact-length execution (bucketing off, graph
        not maskable, or length above the largest pinned bucket — the
        overflow case counts in ``stats["bucket_overflow"]`` and the
        ``service.bucket_overflow`` obs counter, since each one is a
        separate exact-length compile the bucket config failed to
        absorb)."""
        reg = self._graphs[name]
        if not self.bucketing or reg.struct is None:
            return None
        lo = max(length, reg.struct.min_length)
        if self.buckets is not None:
            i = bisect.bisect_left(self.buckets, lo)
            if i == len(self.buckets):
                self.stats["bucket_overflow"] += 1
                if obs.ENABLED:
                    obs.metrics().counter("service.bucket_overflow").inc()
                return None
            return self.buckets[i]
        b = 1
        while b < lo:
            b <<= 1
        return b

    def group_key(self, req: SignalRequest) -> Tuple[str, int]:
        """The request's (graph, compile-length) batch key — computed
        once at submit and cached on the request (requests are immutable
        after submit, and re-registration drops queued requests rather
        than re-keying them).  Caches ``req._bucketed`` alongside, so
        the execution path never re-asks ``bucket_for`` (which would
        double-count overflow)."""
        key = getattr(req, "_group_key", None)
        if key is None:
            length = int(np.asarray(req.samples).shape[-1])
            bucket = self.bucket_for(req.graph, length)
            req._bucketed = bucket is not None
            key = (req.graph, bucket if bucket is not None else length)
            req._group_key = key
        return key

    def exec_fingerprint(self, name: str,
                         length: int) -> Optional[Tuple]:
        """The structural compile-cache key of ``name``'s program at
        ``length`` (:func:`repro.signal.backends.program_cache_key`):
        what the scheduler's cross-graph batching groups by.  ``None``
        when the program cannot be fingerprinted (opaque lambda closure
        — such graphs batch per registry name, as before).  Compiles
        the bucket on first use; cached until re-registration."""
        key = (name, length)
        if key not in self._fp_cache:
            from ..signal.backends import program_cache_key
            compiled = self.compiled_for(name, length)
            self._fp_cache[key] = program_cache_key(self.backend,
                                                    compiled.program)
        return self._fp_cache[key]

    # -- queue --------------------------------------------------------------
    def submit(self, req: SignalRequest) -> None:
        """Validate and enqueue.  ``samples`` must be a real-valued 1-D
        ``(T,)`` array (ints are coerced to float32) long enough for the
        graph's analysis frame — rejected here with a clear error rather
        than failing inside the jitted batch."""
        if req.graph not in self._graphs:
            raise KeyError(f"unknown graph {req.graph!r}")
        reg = self._graphs[req.graph]
        arr = np.asarray(req.samples)
        if arr.ndim != 1:
            raise ValueError(
                f"SignalRequest.samples must be 1-D (T,); got shape "
                f"{arr.shape} for rid={req.rid}")
        if not (np.issubdtype(arr.dtype, np.floating)
                or np.issubdtype(arr.dtype, np.integer)):
            raise TypeError(
                f"SignalRequest.samples must be real-valued; got dtype "
                f"{arr.dtype} for rid={req.rid}")
        if arr.dtype != np.float32:
            arr = arr.astype(np.float32)
        min_len = reg.struct.min_length if reg.struct is not None else 1
        if arr.shape[-1] < min_len:
            raise ValueError(
                f"SignalRequest.samples too short for graph "
                f"{req.graph!r}: {arr.shape[-1]} < {min_len} samples "
                f"(the analysis frame) for rid={req.rid}")
        req.samples = arr
        req.seq = self._seq
        self._seq += 1
        req._group_key = None          # (re-)keyed by THIS service's buckets
        req._exec_key = None           # ditto for the scheduler's grouping
        req._promoted_length = None
        self.group_key(req)
        self._queue.append(req)
        if obs.ENABLED:
            req._admit_ns = obs.now()
            m = obs.metrics()
            m.counter("service.submitted").inc()
            m.gauge("service.queue_depth").set(len(self._queue))

    def pending(self) -> int:
        """Requests not yet completed: the live queue plus rows claimed
        into the scheduler's partially-executed split waves."""
        n = len(self._queue)
        if self.scheduler is not None:
            n += self.scheduler.backlog_rows()
        return n

    def pending_groups(self) -> List[GroupInfo]:
        """Summaries of the queued batch groups, in FIFO order of their
        oldest member (what a policy needs to pick a group)."""
        groups: Dict[Tuple[str, int], List[SignalRequest]] = {}
        for r in self._queue:
            groups.setdefault(self.group_key(r), []).append(r)
        out = [GroupInfo(key=k, count=len(rs),
                         oldest_seq=min(r.seq for r in rs),
                         earliest_deadline=min(r.deadline for r in rs))
               for k, rs in groups.items()]
        out.sort(key=lambda g: g.oldest_seq)
        return out

    def group_cost(self, key: Tuple[str, int], batch: int = 1) -> int:
        """Perf-model cycles for one batched execution of a group
        (compiles the bucket on first use; cached thereafter)."""
        from ..core.perf_model import step_cost_estimate
        if key not in self._cost_cache:
            self._cost_cache[key] = step_cost_estimate(
                self.compiled_for(*key))
        return self._cost_cache[key] * max(1, batch)

    def _charge_devices(self, per_item: int, batch: int) -> int:
        """Charge one wave's per-device cost split to the router ledger
        (:func:`repro.core.perf_model.device_step_costs` — pad rows
        execute, so every shard pays ``ceil(batch/n)`` rows) and return
        the wave's wall-clock cycles: the max per-device share on a
        mesh, the plain total otherwise."""
        if self.router is None:
            return per_item * max(1, batch)
        from ..core.perf_model import device_step_costs
        costs = device_step_costs(per_item, batch, self.router.n_devices)
        for i, c in enumerate(costs):
            if c:
                self.router.charge(i, c)
        if obs.ENABLED:
            obs.tracer().counter(
                "device_occupancy",
                {f"d{i}": c
                 for i, c in enumerate(self.router.device_cycles)})
        return max(costs)

    # -- one-shot batched execution -----------------------------------------
    def _fifo_pick(self, queue: List[SignalRequest]) -> List[SignalRequest]:
        key = self.group_key(queue[0])
        wave = [r for r in queue if self.group_key(r) == key]
        return wave[: self.batch_size]

    def make_pick(self, key: Tuple[str, int],
                  order: str = "fifo") -> Callable:
        """A picker for :meth:`step` selecting ``key``'s group, in FIFO
        or earliest-deadline order."""
        def pick(queue: List[SignalRequest]) -> List[SignalRequest]:
            wave = [r for r in queue if self.group_key(r) == key]
            if order == "deadline":
                wave.sort(key=lambda r: (r.deadline, r.seq))
            return wave[: self.batch_size]
        return pick

    def step(self, pick: Optional[Callable] = None) -> Dict[int, np.ndarray]:
        """Execute ONE batched graph call and return ``{rid: output}``.

        With no explicit ``pick``, the service's :class:`SigSched`
        decides the wave (cross-graph batching by program fingerprint,
        EDF with slack-aware deferral when finite deadlines are queued,
        preemptible row budgets) — with the default configuration and no
        deadlines anywhere this reduces exactly to the legacy pick: the
        oldest request's (graph, bucket) group in arrival order, up to
        ``batch_size``.  Passing ``pick`` (or ``scheduler=False`` at
        construction) bypasses the scheduler entirely.  Admission is
        continuous — requests submitted after earlier steps join
        whichever wave their group forms next.  All requests in a wave
        share one compiled program; shorter requests are zero-padded to
        the bucket and masked, and their outputs trimmed back, equal to
        unpadded execution (bitwise except FIR im2col GEMMs — see the
        module docstring).  Scheduling changes WHEN a request computes,
        never what it computes.
        """
        if pick is None and self.scheduler is not None:
            return self.scheduler.dispatch()
        if not self._queue:
            return {}
        wave = (pick or self._fifo_pick)(list(self._queue))
        if not wave:
            return {}
        return self._execute_wave(wave, self.group_key(wave[0])[1])

    # -- wave execution (what SigSched dispatches into) ----------------------
    def _params_classes(self, wave) -> List[Tuple[object, List[int]]]:
        """Wave rows grouped by their graph's registered params —
        identity first, then exact pytree equality.  One class ==
        every row can share a single params argument."""
        classes: List[Tuple[object, List[int]]] = []
        for i, r in enumerate(wave):
            p = self._graphs[r.graph].params
            for cp, idxs in classes:
                if _params_equal(cp, p):
                    idxs.append(i)
                    break
            else:
                classes.append((p, [i]))
        return classes

    @staticmethod
    def _stackable(classes) -> bool:
        """True when every params class shares one treedef with matching
        leaf shapes/dtypes — the per-row ``vmap`` batching precondition."""
        rep = classes[0][0]
        td = jax.tree_util.tree_structure(rep)
        sig = [(np.asarray(l).shape, np.asarray(l).dtype)
               for l in jax.tree_util.tree_leaves(rep)]
        for p, _ in classes[1:]:
            if jax.tree_util.tree_structure(p) != td:
                return False
            if [(np.asarray(l).shape, np.asarray(l).dtype)
                    for l in jax.tree_util.tree_leaves(p)] != sig:
                return False
        return True

    def _execute_wave(self, wave: List[SignalRequest],
                      length: int) -> Dict[int, np.ndarray]:
        """Pad, stack, execute and trim one wave at compile ``length``.

        This is the half of the old ``step`` below the pick — the
        scheduler dispatches into it (possibly with a wave mixing
        requests from different fingerprint-equal graphs, or a chunk of
        a split wave whose siblings already ran).  Requests still in
        the queue are claimed here; rows keep their own true lengths,
        so masks and trims are identical however the wave was formed.
        Waves mixing rows whose registered params differ execute
        per-row-batched (one jitted ``vmap`` over a stacked params
        pytree) when the pytrees stack, else split into one sub-call
        per params class (``stats["param_splits"]``).

        Spans: ``wave`` around the whole wave, and inside it one per
        phase — ``wave.stack``, ``wave.h2d``, ``wave.launch`` (up to the
        jitted call's asynchronous return), ``wave.fetch`` (the wait for
        the device and the copy back), ``wave.finish`` — all carrying
        the service's wave number ``wave``."""
        n = self._wave_seq
        self._wave_seq += 1
        name = wave[0].graph
        with obs.span("SignalService", "wave", wave=n, graph=name,
                      bucket=length, rows=len(wave)):
            with obs.span("SignalService", "wave.stack", wave=n) as sp:
                for r in wave:
                    try:
                        self._queue.remove(r)
                    except ValueError:
                        pass           # claimed earlier into a split wave
                reg = self._graphs[name]
                compiled = self.compiled_for(name, length)
                key = (name, length)
                lens = [int(r.samples.shape[-1]) for r in wave]
                padded = any(t != length for t in lens)
                bucketed = any(getattr(r, "_bucketed", False) for r in wave)
                masked = padded or (reg.struct is not None
                                    and reg.struct.framer is not None
                                    and bucketed)
                classes = self._params_classes(wave)
                split = len(classes) > 1 and (self.mesh is not None
                                              or not self._stackable(classes))
                if not split:
                    # on a mesh the row count pads to a shard multiple so
                    # the NamedSharding row partition is even; pad rows
                    # are zeros (a valid, row-independent input) and
                    # nothing reads their output.
                    rows = self.mesh.padded_rows(len(wave)) \
                        if self.mesh is not None else len(wave)
                    stack = np.zeros((rows, length), np.float32)
                    for i, r in enumerate(wave):
                        stack[i, : lens[i]] = r.samples
                    # pad waste: the fraction of the stacked (batch,
                    # bucket) array that is zero padding past each row's
                    # true length.
                    if sp or obs.ENABLED:
                        pad_waste = 1.0 - sum(lens) / float(len(wave)
                                                            * length)
                        sp.set(pad_waste=round(pad_waste, 4))
            if split:
                # mismatched params pytrees (or a mesh, whose row sharding
                # the per-row vmap path does not thread): one sub-call per
                # params class — the same batched lowering as per-graph
                # dispatch, so trivially exact.
                self.stats["param_splits"] += len(classes) - 1
                results: Dict[int, np.ndarray] = {}
                for _, idxs in classes:
                    results.update(
                        self._execute_wave([wave[i] for i in idxs], length))
                return results

            with obs.span("SignalService", "wave.h2d", wave=n,
                          bytes=stack.nbytes):
                batch = self.mesh.shard(stack) if self.mesh is not None \
                    else jnp.asarray(stack)
            with obs.span("SignalService", "wave.launch", wave=n):
                out = self._launch(key, compiled, reg, batch, lens, wave,
                                   masked, classes)
            with obs.span("SignalService", "wave.fetch", wave=n) as sp:
                out = _to_host(out)
                if sp or obs.ENABLED:
                    d2h = sum(a.nbytes for a in jax.tree_util.tree_leaves(out))
                    sp.set(bytes=d2h)
            with obs.span("SignalService", "wave.finish", wave=n):
                self.stats["bucketed" if masked else "exact"] += 1
                self.stats["batches"] += 1
                self.est_cycles += self.group_cost(key, batch=len(wave))
                self.wall_cycles += self._charge_devices(self.group_cost(key),
                                                         len(wave))
                results = {}
                for i, r in enumerate(wave):
                    r.done = True
                    results[r.rid] = self._request_result(
                        compiled, self._graphs[r.graph], out, i, lens[i])
                if obs.ENABLED:
                    m = obs.metrics()
                    m.histogram("service.pad_waste").record(pad_waste)
                    m.counter("service.h2d_bytes").inc(stack.nbytes)
                    m.counter("service.d2h_bytes").inc(d2h)
                    self._record_emits(compiled, wave)
            return results

    def _launch(self, key, compiled, reg, batch, lens, wave, masked,
                classes):
        """Call the wave's jitted entry — the per-row-params ``vmap``,
        the masked program or the plain one — and return its device
        result without waiting for it.  The first call of an entry at a
        row count is the one that compiles it: a ``compile`` span."""
        struct = reg.struct
        mask = masked and struct is not None and struct.framer is not None
        if mask:
            # valid-frame counts per row are traced so one compile serves
            # every length mix in the bucket; sharded batches carry zero
            # pad rows past the wave: 0 valid frames masks every frame of
            # a pad row (an all-zero result nothing reads back).
            counts = [struct.valid_frames(t) for t in lens]
            counts += [0] * (batch.shape[0] - len(counts))
            vf = jnp.asarray(counts, jnp.int32)
        if len(classes) > 1:
            entry, call = "vmap", self._vmap_call(key, compiled, mask)
            params = self._row_params(wave)
        elif mask:
            entry, call = "masked", self._masked_call(key, compiled)
            params = classes[0][0]
        else:
            # a pure sample chain (no framer): causal stages never read
            # past a row's valid prefix, so padding needs no masking —
            # only trimming.
            entry, call = "plain", self._plain_call(key, compiled)
            params = classes[0][0]
        args = (batch, vf, params) if mask else (batch, params)
        with self._first_call_span((entry, *key, batch.shape[0]),
                                   graph=key[0], bucket=key[1],
                                   rows=batch.shape[0], entry=entry):
            return call(*args)

    def _row_params(self, wave):
        """The wave's per-row registered params, stacked on a leading
        row axis (the per-row ``vmap``'s params argument)."""
        return jax.tree_util.tree_map(
            lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]),
            *[self._graphs[r.graph].params for r in wave])

    def _vmap_call(self, key, compiled, mask: bool):
        """Cross-graph wave whose member graphs registered DIFFERENT
        params: one jitted ``vmap`` over (row, valid_frames, per-row
        params) — each row computes with its own graph's params, in one
        launch.  ``vmap`` of the row program over the batch axis lowers
        to the same batched einsums as the shared-params call, so
        results stay within the bucketing exactness contract (asserted
        bit-exact for the streamable graph class in
        tests/test_scheduler.py)."""
        vkey = (*key, mask)
        if vkey not in self._vmap_jitted:
            if mask:
                def call(x, vf, p):
                    return compiled(x, p, valid_frames=vf)
            else:
                def call(x, p):
                    return compiled(x, p)
            self._vmap_jitted[vkey] = jax.jit(jax.vmap(call))
        return self._vmap_jitted[vkey]

    def _record_emits(self, compiled, wave) -> None:
        """Admission->emit latency per request, attributed per graph and
        (for multi-output SigPrograms) per output — all of a request's
        outputs emit on the same step, so the per-output series differ
        only once per-output deadlines/taps emit at different times
        (the streaming path).  Cross-graph waves attribute each row to
        its own registered graph name."""
        m = obs.metrics()
        m.gauge("service.queue_depth").set(len(self._queue))
        t_now = obs.now()
        outs = [compiled.output] if compiled.single \
            else list(compiled.outputs)
        for r in wave:
            t_adm = getattr(r, "_admit_ns", None)
            if t_adm is None:
                continue
            lat_us = (t_now - t_adm) / 1e3
            m.histogram(f"service.latency_us.{r.graph}").record(lat_us)
            if len(outs) > 1:
                for o in outs:
                    m.histogram(
                        f"service.latency_us.{r.graph}/{o}").record(lat_us)

    def _request_result(self, compiled, reg, out, i, true_len):
        """Row ``i``'s result, trimmed back to the request's true
        length.  Multi-output graphs yield the ordered per-output dict
        (the SigProgram contract), each output trimmed along its own
        leading suffix axis (frame rows for frames-domain outputs,
        samples otherwise)."""
        def trim(res, name):
            if reg.struct is None:
                return res
            cnt = reg.struct.out_count_for(name, true_len)
            rank = len(compiled.out_types[name].suffix)
            sl = [slice(None)] * res.ndim
            sl[res.ndim - rank] = slice(0, cnt)
            return res[tuple(sl)]
        if compiled.single:
            return trim(out[i], compiled.output)
        return {name: trim(np.asarray(out[name])[i], name)
                for name in compiled.outputs}

    def _plain_call(self, key, compiled):
        """The bucket's unmasked jitted entry point (row-parallel on a
        mesh), compiled once per (graph, bucket)."""
        if key not in self._jitted:
            self._jitted[key] = compiled.jit() if self.mesh is None \
                else self.mesh.row_parallel(compiled.__call__, 1)
        return self._jitted[key]

    def _masked_call(self, key, compiled):
        """The bucket's masked jitted entry point ``(x, valid_frames,
        params)`` (row-parallel on a mesh), compiled once per (graph,
        bucket)."""
        if key not in self._masked_jitted:
            if self.mesh is None:
                self._masked_jitted[key] = compiled.masked_jit()
            else:
                def masked(x, vf, p):
                    return compiled(x, p, valid_frames=vf)
                self._masked_jitted[key] = self.mesh.row_parallel(masked, 2)
        return self._masked_jitted[key]

    def serve(self, requests: List[SignalRequest]) -> Dict[int, np.ndarray]:
        """Drain a request list without an LLM co-tenant."""
        for r in requests:
            self.submit(r)
        results: Dict[int, np.ndarray] = {}
        while self.pending():
            results.update(self.step())
        return results

    # -- per-connection streaming sessions ----------------------------------
    def open_stream(self, name: str,
                    block_frames: Optional[int] = None) -> "StreamSession":
        """Open a streaming connection over a registered graph.  The
        graph must stream (sample chain, or stft -> core -> istft);
        chunked submissions go through :meth:`StreamSession.feed` and
        same-graph sessions' ready blocks execute as ONE jitted core
        call per :meth:`stream_step`."""
        reg = self._graphs.get(name)
        if reg is None:
            raise KeyError(f"unknown graph {name!r}")
        if reg.struct is None or (reg.struct.framer is not None
                                  and reg.struct.deframer is None):
            raise ValueError(f"graph {name!r} is not streamable")
        sess = StreamSession(self, name, self._sid,
                             block_frames or self.block_frames)
        if self.router is not None:
            # device affinity for life: the session's carried state
            # lands on this shard and stays there across ticks.
            sess.device_index = self.router.assign()
        self._sid += 1
        self._sessions.setdefault(name, []).append(sess)
        return sess

    def stream_sessions(self, name: Optional[str] = None) -> int:
        if name is not None:
            return len(self._sessions.get(name, []))
        return sum(len(v) for v in self._sessions.values())

    def stream_pending(self) -> bool:
        """True if any open session has a full block ready to execute."""
        for name, sessions in self._sessions.items():
            struct = self._graphs[name].struct
            for s in sessions:
                if ready_spec(struct, s.state, s.block_frames,
                              final=False) is not None:
                    return True
        return False

    def stream_step(self) -> int:
        """Advance all streaming sessions by at most one block each.
        Ready blocks of sessions with matching shapes stack into ONE
        jitted core call — same-graph always, and ACROSS graphs when the
        scheduler's cross-graph batching is on and the graphs' streamed
        core programs fingerprint identically AND their registered
        params compare equal (the core call threads one shared params
        pytree); each session then overlap-adds its own slice back into
        its carried state.  Returns the number of jitted core calls
        issued (the bench asserts <= 1 per tick per graph for
        lock-stepped sessions)."""
        with obs.span("Streaming", "stream.tick") as sp:
            calls = self._stream_tick()
            if sp:
                sp.set(core_calls=calls, sessions=self.stream_sessions())
        return calls

    def _stream_tick(self) -> int:
        calls = 0
        # per-shard cost of THIS tick: shards run concurrently, so the
        # tick's wall-clock contribution is the max over shards.
        tick_costs: Dict[Optional[int], int] = {}
        cross = (self.scheduler is not None and self.scheduler.cross_graph
                 and len(self._sessions) > 1)
        groups: Dict[Tuple, List[Tuple[str, "StreamSession", object,
                                       jax.Array]]] = {}
        for name, sessions in self._sessions.items():
            struct = self._graphs[name].struct
            for sess in sessions:
                spec = ready_spec(struct, sess.state, sess.block_frames,
                                  final=False)
                if spec is None:
                    continue
                block = take_block(sess.state, spec)
                ident: Tuple = ("graph", name)
                if cross:
                    fp = self._stream_fp(name, spec.n_frames)
                    if fp is not None:
                        ident = ("fp", fp)
                # device affinity is part of the stacking key: a stacked
                # call only ever mixes sessions homed on the same shard,
                # so no carried state migrates to serve a batch.
                gkey = (ident, spec.n_frames, block.shape,
                        block.dtype.name, sess.device_index)
                groups.setdefault(gkey, []).append((name, sess, spec,
                                                    block))
        for (ident, n_frames, _, _, dev), members in groups.items():
            # params ride the stacked core call as ONE shared pytree, so
            # a fingerprint group sub-partitions by params equality —
            # fp-equal graphs with different weights never mix.
            for sub in self._stream_params_split(members):
                rep_name = sub[0][0]
                reg = self._graphs[rep_name]
                struct = reg.struct
                gnames = sorted({n for n, *_ in sub})
                with obs.span("Streaming", "stream.core", graph=rep_name,
                              graphs="+".join(gnames), n_frames=n_frames,
                              width=len(sub),
                              device=-1 if dev is None else dev):
                    stacked = jnp.stack([b for *_, b in sub])
                    if self.mesh is not None and dev is not None:
                        stacked = jax.device_put(stacked,
                                                 self.mesh.device_for(dev))
                    core = struct.core_jit(n_frames, self.fuse, self.backend)
                    with self._first_call_span(
                            ("core", rep_name, n_frames, len(sub)),
                            graph=rep_name, n_frames=n_frames,
                            rows=len(sub), entry="core"):
                        res = core(stacked, reg.params)
                calls += 1
                if len(gnames) > 1:
                    self.scheduler.stats["cross_graph_batches"] += 1
                    if obs.ENABLED:
                        obs.metrics().counter(
                            "sched.cross_graph_batches").inc()
                if obs.ENABLED:
                    obs.metrics().histogram(
                        "service.stream_stack_width").record(len(sub))
                cost = sum(self._stream_cost(n, n_frames)
                           for n, *_ in sub)
                self.est_cycles += cost
                tick_costs[dev] = tick_costs.get(dev, 0) + cost
                if self.router is not None and dev is not None:
                    self.router.charge(dev, cost)
                for i, (name, sess, spec, block) in enumerate(sub):
                    sreg = self._graphs[name]
                    sstruct = sreg.struct
                    # fp-equal programs share stage/output names (the
                    # digest pins them), so rep's result dict keys are
                    # valid for every member's own struct.
                    if isinstance(res, dict):
                        frames = res[sstruct.deframer][i]
                        taps = {t: tap_rows(res[t][i], spec,
                                            block.ndim - 1)
                                for t in sstruct.frame_outputs}
                    else:
                        frames, taps = res[i], {}
                    st, piece = commit_frames(sstruct, sess.state, spec,
                                              frames, final=False)
                    st, out = finalize_piece(sstruct, st, piece,
                                             final=False,
                                             params=sreg.params)
                    sess.state = st
                    if sstruct.single:
                        sess._push_out(out)
                    else:
                        merged = dict(out) if isinstance(out, dict) else {}
                        merged.update(taps)
                        sess._push_outs(merged)
        if tick_costs:
            self.wall_cycles += max(tick_costs.values())
            if obs.ENABLED and self.router is not None:
                obs.tracer().counter(
                    "device_occupancy",
                    {f"d{i}": c
                     for i, c in enumerate(self.router.device_cycles)})
        if calls:
            self.stats["core_calls"] += calls
        self.stats["stream_ticks"] += 1
        return calls

    def _stream_fp(self, name: str, n_frames: int) -> Optional[Tuple]:
        """Fingerprint-keyed cache key of ``name``'s streamed CORE
        program at ``n_frames`` — the stream-side analog of
        :meth:`exec_fingerprint` (``None`` when the core cannot be
        fingerprinted: such sessions stack per graph name, as before).
        Cached until re-registration (the ``//core`` rows purge with
        the cost cache)."""
        key = (f"{name}//core", n_frames)
        if key not in self._fp_cache:
            from ..signal.backends import program_cache_key
            struct = self._graphs[name].struct
            compiled = struct.core_graph(n_frames, self.fuse,
                                         self.backend)
            self._fp_cache[key] = program_cache_key(self.backend,
                                                    compiled.program)
        return self._fp_cache[key]

    def _stream_params_split(self, members):
        """Partition one stream stacking group by registered-params
        equality (identity fast-path first) — each partition shares one
        params pytree, preserving per-member order."""
        parts: List[Tuple[object, List]] = []
        for m in members:
            p = self._graphs[m[0]].params
            for cp, sub in parts:
                if _params_equal(cp, p):
                    sub.append(m)
                    break
            else:
                parts.append((p, [m]))
        return [sub for _, sub in parts]

    def _stream_cost(self, name: str, n_frames: int) -> int:
        """Perf-model cycles for one session's core block (cached)."""
        from ..core.perf_model import step_cost_estimate
        key = (f"{name}//core", n_frames)
        if key not in self._cost_cache:
            struct = self._graphs[name].struct
            self._cost_cache[key] = step_cost_estimate(
                struct.core_graph(n_frames, self.fuse, self.backend))
        return self._cost_cache[key]

    def _close_stream(self, sess: "StreamSession") -> None:
        lst = self._sessions.get(sess.graph_name, [])
        if sess in lst:
            lst.remove(sess)
            if self.router is not None:
                self.router.release(sess.device_index)

    # -- checkpoint / restore (the fault-tolerance contract) ----------------
    def session_by_sid(self, sid: int) -> Optional["StreamSession"]:
        for sessions in self._sessions.values():
            for s in sessions:
                if s.sid == sid:
                    return s
        return None

    def checkpoint(self) -> Dict:
        """Host-side snapshot of every open streaming session (carried
        state, pending unread output, delivery counters, device
        affinity) plus the service counters.  Plain numpy throughout —
        independent of device health, cheap enough to take per tick.
        One-shot queue entries are NOT captured (they are client-owned
        request objects, resubmittable by contract); streaming state is
        what only the service can reconstruct.  Restoring follows
        :class:`repro.runtime.fault_tolerance.TrainLoop`'s contract:
        state rewinds, inputs replay, and the resumed stream is
        bit-identical (the StreamSupervisor journals feeds for the
        replay half)."""
        sessions = [s.snapshot() for ss in self._sessions.values()
                    for s in ss]
        return {"format": 1,
                "sid": self._sid,
                "sessions": sessions,
                "est_cycles": self.est_cycles,
                "wall_cycles": self.wall_cycles,
                "device_cycles": list(self.router.device_cycles)
                if self.router is not None else None}

    def restore(self, ckpt: Dict) -> None:
        """Restore the streaming side to a :meth:`checkpoint`.  Live
        session handles are restored IN PLACE (client code keeps its
        ``StreamSession`` objects); sessions opened after the
        checkpoint are detached with an explanatory ``error``; sessions
        homed on a since-dropped shard are re-homed by the router.
        Delivery counters are merged, not rewound — data a client
        already ``read()`` is never emitted twice after the replay
        (exactly-once delivery; see :meth:`StreamSession._dedup`)."""
        live = {s.sid: s for ss in self._sessions.values() for s in ss}
        self._sessions = {}
        restored = set()
        for snap in ckpt["sessions"]:
            name = snap["graph"]
            if name not in self._graphs:
                raise KeyError(f"cannot restore session {snap['sid']}: "
                               f"graph {name!r} is not registered")
            sess = live.get(snap["sid"])
            if sess is None:
                sess = StreamSession(self, name, snap["sid"],
                                     snap["block_frames"])
            sess._load_snapshot(snap)
            self._sessions.setdefault(name, []).append(sess)
            restored.add(snap["sid"])
        for sid, sess in live.items():
            if sid not in restored and not sess.closed:
                sess.closed = True
                sess.error = ("service restored to a checkpoint taken "
                              "before this session was opened")
                self.stats["detached_sessions"] += 1
        self._sid = max(self._sid, int(ckpt["sid"]))
        self.est_cycles = ckpt.get("est_cycles", self.est_cycles)
        self.wall_cycles = ckpt.get("wall_cycles", self.wall_cycles)
        dc = ckpt.get("device_cycles")
        if self.router is not None and dc is not None \
                and len(dc) == self.router.n_devices:
            self.router.device_cycles = [int(c) for c in dc]

    def save_checkpoint(self, directory: str, step: Optional[int] = None,
                        keep: int = 3, blocking: bool = True) -> int:
        """Persist :meth:`checkpoint` to disk through
        :class:`repro.checkpoint.Checkpointer` (atomic tmp+rename dirs,
        COMMIT markers, keep-N retention) so streams survive process
        death.  Snapshot dicts mix numpy arrays with strings / ints /
        ``StreamState`` counters, so the arrays are stored as manifest
        leaves and the surrounding structure rides the manifest's JSON
        ``meta`` sidecar.  Returns the step number written."""
        from ..checkpoint.checkpointer import Checkpointer
        snap = self.checkpoint()
        if step is None:
            step = self._ckpt_seq
        self._ckpt_seq = step + 1
        enc, leaves = _ckpt_encode(snap)
        with obs.span("SignalService", "checkpoint", step=step,
                      leaves=len(leaves), sessions=len(snap["sessions"])):
            Checkpointer(directory, keep=keep).save(step, leaves,
                                                    blocking=blocking,
                                                    meta=enc)
        return step

    def restore_from_disk(self, directory: str,
                          step: Optional[int] = None) -> int:
        """Template-free restore of :meth:`save_checkpoint` (default:
        the latest committed step) — the process-death path: a fresh
        service with the same graphs registered rebuilds every session
        from disk, with the same exactly-once delivery merge as
        :meth:`restore`.  Returns the step restored."""
        from ..checkpoint.checkpointer import Checkpointer
        step, leaves, enc = Checkpointer(directory).restore(
            like=None, step=step, with_meta=True)
        if enc is None:
            raise ValueError(
                f"checkpoint step {step} in {directory!r} has no "
                f"structure sidecar; was it written by save_checkpoint?")
        self.restore(_ckpt_decode(enc, [np.asarray(a) for a in leaves]))
        self._ckpt_seq = max(self._ckpt_seq, step + 1)
        return step

    def drop_device(self, index: int) -> None:
        """Simulated device loss: mark the shard dead in the router and
        re-home its sessions onto surviving shards (their carried state
        moves once — affinity then holds on the new shard)."""
        if self.router is None:
            raise ValueError("drop_device needs a meshed service")
        self.router.drop(index)
        moved = 0
        for sessions in self._sessions.values():
            for sess in sessions:
                if sess.device_index == index:
                    self.router.release(index)
                    sess.device_index = self.router.assign()
                    sess.state = jax.device_put(
                        sess.state,
                        self.mesh.device_for(sess.device_index))
                    moved += 1
        self.stats["device_losses"] = self.stats.get("device_losses",
                                                     0) + 1
        if obs.ENABLED:
            obs.instant("SignalService", "device_loss", device=index,
                        sessions_moved=moved)


class StreamSession:
    """One streaming connection to a :class:`SignalService`.

    ``feed(chunk)`` pushes samples through the connection's sample-domain
    pre-chain into its ring buffer (cheap, host-side); the heavy framed
    core runs when the service batches ready blocks across sessions in
    :meth:`SignalService.stream_step`.  ``read()`` pops the samples that
    became final; ``close()`` drains the remainder (including the
    overlap-add tail) and returns everything unread.  The concatenated
    ``read()``/``close()`` stream is bit-identical to a private
    :class:`StreamingRunner` (they share one drain implementation) and
    matches the graph's offline execution under the streaming runtime's
    exactness contract (bitwise; FIR stages to float32 ULPs).
    """

    def __init__(self, service: SignalService, name: str, sid: int,
                 block_frames: int):
        self.service = service
        self.graph_name = name
        self.sid = sid
        self.block_frames = int(block_frames)
        self.state = StreamState()
        self.closed = False
        self.error: Optional[str] = None      # set when force-detached
        self.device_index: Optional[int] = None   # shard affinity (mesh)
        self._out: List[np.ndarray] = []
        self._outs: Dict[str, List[np.ndarray]] = {}
        # exactly-once delivery counters, in absolute stream positions
        # along each output's frames/time axis: ``_pushed`` = data ever
        # produced into the pending lists, ``_delivered`` = data handed
        # to the client by read()/close().  A checkpoint restore rewinds
        # _pushed with the state; _delivered is connection memory and
        # survives, so replayed ticks re-produce — and _dedup drops —
        # exactly the already-delivered prefix.  Single-output sessions
        # use the key None.
        self._pushed: Dict[Optional[str], int] = {}
        self._delivered: Dict[Optional[str], int] = {}

    @property
    def _reg(self) -> _Registration:
        return self.service._graphs[self.graph_name]

    @property
    def single(self) -> bool:
        """True when the graph uses the deprecated single-output
        contract (``read``/``close`` return bare arrays)."""
        return self._reg.struct.single

    def feed(self, chunk) -> None:
        """Push one chunk (last axis = time; chunk lengths may vary)."""
        if self.closed:
            raise ValueError(self.error or f"session {self.sid} is closed")
        self.state, out = push_chunk(self._reg.struct, self.state, chunk,
                                     self._reg.params)
        if isinstance(out, dict):        # multi-output: chain taps emit now
            self._push_outs(out)
        elif out is not None:            # pure sample chain: no latency
            self._push_out(out)

    def _dedup(self, key: Optional[str], arr: np.ndarray,
               axis: int) -> np.ndarray:
        """Exactly-once delivery filter: advance the pushed counter and
        drop the piece's already-delivered prefix.  A no-op on a live
        stream (delivered never exceeds pushed); after a checkpoint
        restore, replayed ticks re-produce data the client already
        read, and this is where it disappears."""
        n = int(arr.shape[axis])
        start = self._pushed.get(key, 0)
        self._pushed[key] = start + n
        skip = min(n, max(0, self._delivered.get(key, 0) - start))
        if skip:
            sl = [slice(None)] * arr.ndim
            sl[axis] = slice(skip, None)
            arr = arr[tuple(sl)]
        return arr

    def _push_out(self, out) -> None:
        arr = self._dedup(None, np.asarray(out), -1)
        if arr.shape[-1]:
            self._out.append(arr)

    def _push_outs(self, outs: Dict) -> None:
        for name, piece in outs.items():
            arr = np.asarray(piece)
            axis = self._frames_axis(name, arr)
            arr = self._dedup(name, arr, axis)
            if arr.shape[axis]:
                self._outs.setdefault(name, []).append(arr)

    def _frames_axis(self, name: str, arr: np.ndarray) -> int:
        """Concatenation axis for an output's pieces: the frames axis
        for frame taps (right after the connection's batch axes, whose
        rank the ring buffer knows), the time axis otherwise."""
        struct = self._reg.struct
        if name in struct.frame_outputs and self.state.buf is not None:
            return self.state.buf.ndim - 1
        return arr.ndim - 1

    def frames_ready(self) -> int:
        """Frames currently executable without more input (lookahead
        held back, as in non-final streaming)."""
        struct = self._reg.struct
        if struct.framer is None:
            return 0
        spec = ready_spec(struct, self.state, 10 ** 9, final=False)
        return 0 if spec is None else spec.count

    def read(self):
        """Pop the output data that became final so far.  Single-output
        sessions return the bare sample array; multi-output sessions
        return a dict of the outputs with new data (per-output pieces
        concatenated along their frames/time axis)."""
        if self.single:
            if not self._out:
                shape = (*self.state.batch_shape, 0) \
                    if self.state.buf is None \
                    else (*self.state.buf.shape[:-1], 0)
                return np.zeros(shape, np.float32)
            out = self._out[0] if len(self._out) == 1 else np.concatenate(
                self._out, axis=-1)
            self._out = []
            # everything pushed is now in the client's hands
            self._delivered[None] = self._pushed.get(None, 0)
            return out
        outs = {}
        for name, pieces in self._outs.items():
            axis = self._frames_axis(name, pieces[0])
            outs[name] = pieces[0] if len(pieces) == 1 \
                else np.concatenate(pieces, axis=axis)
            self._delivered[name] = self._pushed.get(name, 0)
        self._outs = {}
        return outs

    def close(self):
        """Flush: run the remaining frames (per-session — tails have
        irregular shapes), emit the overlap-add tail, detach from the
        service, and return everything unread."""
        if self.closed:
            return self.read()
        self.closed = True
        struct, reg = self._reg.struct, self._reg
        if struct.framer is not None:
            svc = self.service

            def run_core(block, n_frames):
                cost = svc._stream_cost(self.graph_name, n_frames)
                svc.est_cycles += cost
                svc.wall_cycles += cost
                if svc.router is not None \
                        and self.device_index is not None:
                    svc.router.charge(self.device_index, cost)
                svc.stats["flush_core_calls"] += 1
                res = struct.core_jit(n_frames, svc.fuse, svc.backend)(
                    block[None], reg.params)
                return jax.tree_util.tree_map(lambda a: a[0], res)

            self.state, out = drain_state(struct, self.state,
                                          self.block_frames, run_core,
                                          final=True, params=reg.params)
            if isinstance(out, dict):
                self._push_outs(out)
            elif out is not None:
                self._push_out(out)
        self.service._close_stream(self)
        return self.read()

    # -- checkpoint / restore ------------------------------------------------
    def snapshot(self) -> Dict:
        """Plain-data (host numpy) snapshot of this connection: carried
        state, pending unread output, exactly-once delivery counters,
        and shard affinity.  Deep copies throughout — the snapshot is
        valid after any amount of further streaming, and after losing
        the device the live state was homed on."""
        return {
            "sid": self.sid,
            "graph": self.graph_name,
            "block_frames": self.block_frames,
            "device_index": self.device_index,
            "closed": self.closed,
            "error": self.error,
            "state": snapshot_state(self.state),
            "pending": [np.array(a) for a in self._out],
            "pendings": {k: [np.array(a) for a in v]
                         for k, v in self._outs.items()},
            "pushed": dict(self._pushed),
            "delivered": dict(self._delivered),
        }

    def _load_snapshot(self, snap: Dict) -> None:
        """Restore this connection in place from :meth:`snapshot`.  The
        carried state lands back on the session's affinity shard
        (re-homed first if that shard was dropped).  Pending output is
        re-pushed through the exactly-once filter, and the delivery
        counter keeps the live handle's progress — a client that read
        past the checkpoint sees no duplicates when replay catches the
        stream back up."""
        svc = self.service
        self.block_frames = int(snap["block_frames"])
        self.closed = bool(snap["closed"])
        self.error = snap["error"]
        self.device_index = snap["device_index"]
        device = None
        if svc.mesh is not None and self.device_index is not None:
            if svc.router is not None \
                    and not svc.router.alive[self.device_index]:
                svc.router.release(self.device_index)
                self.device_index = svc.router.assign()
            device = svc.mesh.device_for(self.device_index)
        self.state = restore_state(snap["state"], device=device)
        # delivery memory merges forward: a fresh process takes the
        # checkpoint's counters, a live handle keeps what its client
        # already consumed (the larger of the two).
        delivered = dict(snap["delivered"])
        for k, v in self._delivered.items():
            delivered[k] = max(delivered.get(k, 0), v)
        self._delivered = delivered
        # re-push the checkpoint's pending pieces through the filter:
        # rewind the pushed counters by their extents, then push in
        # order — already-delivered prefixes drop out in _dedup.
        self._pushed = dict(snap["pushed"])
        self._out, self._outs = [], {}
        pend = [np.asarray(a) for a in snap["pending"]]
        if pend:
            self._pushed[None] = self._pushed.get(None, 0) \
                - sum(a.shape[-1] for a in pend)
            for a in pend:
                self._push_out(a)
        for name, pieces in snap["pendings"].items():
            pieces = [np.asarray(a) for a in pieces]
            axes = [self._frames_axis(name, a) for a in pieces]
            self._pushed[name] = self._pushed.get(name, 0) \
                - sum(a.shape[ax] for a, ax in zip(pieces, axes))
            for a in pieces:
                self._push_outs({name: a})


# --------------------------------------------------------------------------
# LLM + DSP co-scheduling policies
# --------------------------------------------------------------------------

@dataclasses.dataclass
class TickPlan:
    """What one CoScheduler tick should do, as decided by a policy."""
    run_llm: bool = True
    run_dsp: bool = True                       # one-shot DSP batch
    run_streams: Optional[bool] = None         # session block round
    admit: bool = False                        # mid-flight LLM admission
    dsp_key: Optional[Tuple[str, int]] = None  # group to run (None: FIFO)
    dsp_order: str = "fifo"                    # "fifo" | "deadline"
    dsp_sched: bool = False                    # prefer SigSched dispatch
    # dsp_sched=True: when the service carries a SigSched, let IT pick
    # the wave (cross-graph batching, bounded deferral, row budgets) —
    # dsp_key/dsp_order stay filled as the fallback for services built
    # with scheduler=False (and for tests driving make_pick directly).

    def __post_init__(self):
        if self.run_streams is None:           # default: ride with DSP
            self.run_streams = self.run_dsp


class SchedulePolicy:
    """Decides, each tick, which workload classes run and how the DSP
    wave is picked.  Implement :meth:`plan`; the scheduler exposes its
    queues / wave / occupancy counters for inspection."""

    name = "base"

    def plan(self, sched: "CoScheduler") -> TickPlan:
        raise NotImplementedError


class RoundRobinPolicy(SchedulePolicy):
    """The original behaviour: every tick runs one LLM decode step AND
    one FIFO DSP batch, with LLM waves admitted only between waves.
    Kept as the reference policy — existing tests pin it byte-for-byte."""

    name = "round_robin"

    def plan(self, sched: "CoScheduler") -> TickPlan:
        return TickPlan(run_llm=True, run_dsp=True, admit=False)


class LatencyAwarePolicy(SchedulePolicy):
    """Earliest-deadline-first across both workload classes: each tick
    runs the single workload whose most urgent pending request has the
    earliest *finite* deadline.  On a deadline tie (typically ``inf`` ==
    ``inf`` — nobody declared an SLO) the tick degrades to round-robin,
    both sides running in arrival order, so deadline-less traffic can
    never be starved by the other class.  Streaming sessions carry no
    deadline; their ready blocks ride along on every non-DSP tick.  LLM
    newcomers join the active wave mid-flight when slots free up — on
    LLM ticks, since admission itself costs a (re-)prefill and a
    DSP-only tick must not spend the array on one."""

    name = "latency_aware"

    def plan(self, sched: "CoScheduler") -> TickPlan:
        groups = sched.signals.pending_groups()
        dsp_dl = min((g.earliest_deadline for g in groups),
                     default=math.inf)
        llm_dl = sched.llm_earliest_deadline()
        have_llm = sched.llm_pending()
        if not groups:
            # no one-shot DSP wave to race: LLM advances, and any ready
            # stream blocks ride along (streams carry no deadline — they
            # must neither starve nor starve the token side).
            return TickPlan(run_llm=True, run_dsp=False,
                            run_streams=sched.signals.stream_pending(),
                            admit=True)
        best = min(groups, key=lambda g: (g.earliest_deadline,
                                          g.oldest_seq))
        if not have_llm or dsp_dl < llm_dl:
            # admit=False: admission re-prefills, an LLM-side action a
            # DSP-only tick must not perform (tick() honors admit only
            # when run_llm is set, for the same reason).
            return TickPlan(run_llm=False, run_dsp=True, admit=False,
                            dsp_key=best.key, dsp_order="deadline",
                            dsp_sched=True)
        if llm_dl < dsp_dl:
            # streaming blocks still ride along: real-time connections
            # can never starve behind deadline-bearing token traffic.
            return TickPlan(run_llm=True, run_dsp=False, run_streams=True,
                            admit=True)
        # deadline tie: round-robin the tick so neither class starves.
        return TickPlan(run_llm=True, run_dsp=True, admit=True,
                        dsp_key=best.key, dsp_order="deadline",
                        dsp_sched=True)


class CostBalancedPolicy(SchedulePolicy):
    """Keep the accelerator-occupancy split between DSP and decode near
    ``dsp_target`` (fraction of estimated array cycles spent on DSP),
    using :func:`repro.core.perf_model.step_cost_estimate` for compiled
    graphs and ``ServingEngine.decode_step_cost`` for decode steps.
    Each tick runs the side that is furthest below its target share —
    under skewed load this shifts the interleave instead of blindly
    alternating (the paper's §V utilization argument at serving scope)."""

    name = "cost_balanced"

    def __init__(self, dsp_target: float = 0.5):
        if not 0.0 < dsp_target < 1.0:
            raise ValueError("dsp_target must be in (0, 1)")
        self.dsp_target = float(dsp_target)

    def plan(self, sched: "CoScheduler") -> TickPlan:
        have_llm = sched.llm_pending()
        have_dsp = (sched.signals.pending() > 0
                    or sched.signals.stream_pending())
        if not (have_llm and have_dsp):
            return TickPlan(run_llm=have_llm, run_dsp=have_dsp, admit=True)
        total = sched.llm_cycles + sched.dsp_cycles
        dsp_share = sched.dsp_cycles / total if total else 0.0
        if dsp_share < self.dsp_target:
            # admit=False on DSP-only ticks: admission re-prefills (an
            # LLM-side cost this tick chose not to pay).
            return TickPlan(run_llm=False, run_dsp=True, admit=False)
        return TickPlan(run_llm=True, run_dsp=False, admit=True)


_POLICIES = {p.name: p for p in
             (RoundRobinPolicy, LatencyAwarePolicy, CostBalancedPolicy)}


def get_policy(policy: Union[str, SchedulePolicy]) -> SchedulePolicy:
    """Resolve a policy name ('round_robin' | 'latency_aware' |
    'cost_balanced') or pass an instance through."""
    if isinstance(policy, SchedulePolicy):
        return policy
    try:
        return _POLICIES[policy]()
    except KeyError:
        raise ValueError(
            f"unknown policy {policy!r}; choose from "
            f"{sorted(_POLICIES)} or pass a SchedulePolicy instance")


# --------------------------------------------------------------------------
# The co-scheduler
# --------------------------------------------------------------------------

class CoScheduler:
    """One step loop over two workload classes on the same device(s).

    Each :meth:`tick` asks the :class:`SchedulePolicy` for a
    :class:`TickPlan` and then runs (a) one LLM decode step for the
    active token wave and/or (b) one batched DSP execution plus one
    streaming-session block round — the serving analogue of the paper's
    DLA interleaving signal tasks with DNN layers instead of farming
    them out to a separate DSP chip.

    Occupancy accounting: ``llm_cycles`` / ``dsp_cycles`` accumulate the
    perf-model cost estimates of every step executed, which is what the
    ``cost_balanced`` policy steers and the serving bench reports.
    """

    def __init__(self, engine: ServingEngine, signals: SignalService,
                 policy: Union[str, SchedulePolicy] = "round_robin"):
        self.engine = engine
        self.signals = signals
        self.policy = get_policy(policy)
        self._llm_queue: List[Request] = []
        self._wave: Optional[DecodeWave] = None
        self.llm_results: Dict[int, List[int]] = {}
        self.dsp_results: Dict[int, np.ndarray] = {}
        self.ticks = 0
        self.llm_cycles = 0
        self.dsp_cycles = 0

    # -- submission ---------------------------------------------------------
    def submit_llm(self, req: Request) -> None:
        self._llm_queue.append(req)

    def submit_signal(self, req: SignalRequest) -> None:
        self.signals.submit(req)

    # -- introspection (used by policies) -----------------------------------
    def llm_pending(self) -> bool:
        return self._wave is not None or bool(self._llm_queue)

    def llm_earliest_deadline(self) -> float:
        dls = [r.deadline for r in self._llm_queue]
        if self._wave is not None:
            dls.extend(r.deadline for r in self._wave.reqs)
        return min(dls, default=math.inf)

    def occupancy(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {
            "llm_cycles": self.llm_cycles,
            "dsp_cycles": self.dsp_cycles}
        total = self.llm_cycles + self.dsp_cycles
        out["dsp_share"] = self.dsp_cycles / total if total else 0.0
        if self.signals.router is not None:
            # per-device view of the DSP side: the mesh router's ledger
            # (offered cycles per shard, liveness) — what the serving
            # bench's --mesh sweep and the straggler monitor read.
            out["per_device"] = self.signals.router.occupancy()
        return out

    @property
    def idle(self) -> bool:
        return (self._wave is None and not self._llm_queue
                and not self.signals.pending()
                and not self.signals.stream_pending())

    # -- the step loop ------------------------------------------------------
    def _charge_prefill(self) -> None:
        """Prefill processes ``prefill_tokens`` positions for the whole
        batch — first-order, that is one decode-step cost per token."""
        self.llm_cycles += (self.engine.decode_step_cost(self._wave.size)
                            * max(1, self._wave.prefill_tokens))

    def tick(self) -> None:
        with obs.span("CoScheduler", "cosched.tick", tick=self.ticks) as sp:
            plan = self.policy.plan(self)
            if sp:
                sp.set(policy=self.policy.name, run_llm=plan.run_llm,
                       run_dsp=plan.run_dsp, run_streams=plan.run_streams,
                       admit=plan.admit)
            self._tick(plan)
        self.ticks += 1
        if obs.ENABLED:
            self._record_tick()

    def _tick(self, plan: TickPlan) -> None:
        """One tick's work under ``plan``."""
        # LLM side (gated by the plan — a DSP-only tick must not spend
        # the array on a prefill): start a wave between waves, or admit
        # newcomers into a running wave when the policy allows it.
        if plan.run_llm:
            if self._wave is None and self._llm_queue:
                wave = self._llm_queue[: self.engine.batch_size]
                self._llm_queue = self._llm_queue[self.engine.batch_size:]
                self._wave = DecodeWave(self.engine, wave)
                self._charge_prefill()
            elif (plan.admit and self._wave is not None and self._llm_queue
                  and self.engine.temperature <= 0.0):
                free = self._wave.free_slots()
                if free > 0:
                    newcomers = self._llm_queue[:free]
                    self._llm_queue = self._llm_queue[free:]
                    self.llm_results.update(self._wave.admit(newcomers))
                    self._charge_prefill()      # admission re-prefills
        if plan.run_llm and self._wave is not None:
            self._wave.step()
            self.llm_cycles += self.engine.decode_step_cost(self._wave.size)
            self.llm_results.update(self._wave.pop_done())
            if self._wave.done:
                self.llm_results.update(self._wave.results())
                self._wave = None

        # DSP side: one batched one-shot wave and/or one streaming block
        # round (streams can ride along on LLM ticks — latency_aware
        # keeps real-time connections from starving behind token work).
        before = self.signals.est_cycles
        if plan.run_dsp:
            pick = None
            if plan.dsp_key is not None and not (
                    plan.dsp_sched and self.signals.scheduler is not None):
                pick = self.signals.make_pick(plan.dsp_key, plan.dsp_order)
            self.dsp_results.update(self.signals.step(pick=pick))
        if plan.run_streams:
            self.signals.stream_step()
        self.dsp_cycles += self.signals.est_cycles - before

    def _record_tick(self) -> None:
        """One tick's counters: the DSP/LLM occupancy counter track and
        per-backend plan-cache hit-rate tracks."""
        occ = self.occupancy()
        tr = obs.tracer()
        tr.counter("occupancy", {"dsp_cycles": self.dsp_cycles,
                                 "llm_cycles": self.llm_cycles})
        tr.counter("dsp_share", {"share": occ["dsp_share"]})
        if "per_device" in occ:
            per = occ["per_device"]
            tr.counter("device_occupancy",
                       {f"d{i}": c
                        for i, c in enumerate(per["device_cycles"])})
        m = obs.metrics()
        m.gauge("sched.dsp_share").set(occ["dsp_share"])
        m.counter("sched.ticks").inc()
        from ..signal import plan_cache_info
        for label, b in plan_cache_info()["by_backend"].items():
            total = b["hits"] + b["misses"]
            tr.counter(f"plan_cache/{label}",
                       {"hit_rate": b["hits"] / total if total else 0.0})

    def run(self) -> Tuple[Dict[int, List[int]], Dict[int, np.ndarray]]:
        while not self.idle:
            self.tick()
        return self.llm_results, self.dsp_results
