"""Serving-level benchmark: continuous-batched DSP + LLM co-scheduling.

Simulates an offered load of mixed-length DSP requests and LLM decode
requests against one :class:`CoScheduler` per policy, measuring

  * request latency (p50 / p95, in perf-model accelerator cycles from
    arrival to completion — the virtual clock is the cumulative cost of
    everything the scheduler executed);
  * the DSP/DL array-occupancy split at the end of the offered window
    (the knob ``cost_balanced`` steers; under the default skewed load the
    round-robin split collapses onto the DSP side while ``cost_balanced``
    holds its target);
  * streaming sessions: N concurrent connections fed in lock-step, with
    the jitted-core-calls-per-tick ratio (<= 1 for same-graph sessions —
    the batched-chunk-step acceptance number);
  * with ``--sched`` (implied by ``--smoke``): the SigSched sweep — an
    identical mixed-deadline offered load driven through the bare
    SignalService tick with the scheduler on vs off, reporting p50/p95
    admission->emit latency (perf-model cycles) for the
    deadline-bearing requests; ``--smoke`` asserts the scheduled p95
    improves by >= 25% at equal throughput;
  * with ``--mesh 1,8``: the SigMesh sweep — the same drain through an
    unsharded and an N-sharded service, each shard count in its own
    subprocess with that many forced host devices, reporting p50/p95
    wall-cycle latency, per-device occupancy, and the bitwise
    sharded-vs-unsharded ``match`` flag (``--smoke`` asserts it).

Output: one CSV block per section (like the other benches) and, with
``--json PATH``, a machine-readable summary.  With ``--trace PATH`` (or
``REPRO_TRACE=1`` / ``REPRO_TRACE=<path>`` in the environment) the whole
sweep runs under the SigTrace instrumentation: a Perfetto-loadable
Chrome trace is exported and validated, and the post-run
latency/occupancy report is printed after the CSV blocks.

    PYTHONPATH=src python -m benchmarks.signal_service_bench [--smoke]
        [--trace artifacts/service_trace.json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

FRAME, HOP, MAXLEN = 64, 32, 512
POLICIES = ("round_robin", "latency_aware", "cost_balanced")
DSP_TARGET = 0.5
BENCH_SCHEMA_VERSION = 3       # v3: "sched_sweep" section (SigSched)


def _graph():
    from repro.signal import SignalGraph

    g = SignalGraph("fig9_small")
    g.stft("spec", frame=FRAME, hop=HOP)
    g.dnn("mask", "spec", fn=lambda p, z: jax.nn.sigmoid(jnp.abs(z) - 1.0))
    g.mul("enh", "spec", "mask")
    g.istft("out", "enh", hop=HOP)
    g.outputs("out")
    return g


def _engine():
    from repro.configs import get_config
    from repro.models.zoo import get_model
    from repro.serving import ServingEngine

    cfg = get_config("starcoder2-3b").reduced(
        n_layers=2, d_model=32, n_heads=4, d_ff=64, vocab=128)
    bundle = get_model(cfg)
    eng = ServingEngine(bundle, batch_size=2)
    eng.load(bundle.init(jax.random.PRNGKey(0)))
    return eng


def simulate(policy: str, ticks: int, dsp_per_tick: float,
             llm_per_tick: float, seed: int = 0):
    """Open-loop offered load for ``ticks`` scheduler ticks, then drain.
    Latency clock = cumulative perf-model cycles of executed work.
    Returns ``(record, scheduler)`` — the scheduler so the tracing path
    can build the occupancy section of the post-run report."""
    from repro.serving import (CoScheduler, CostBalancedPolicy, Request,
                               SignalRequest, SignalService)

    eng = _engine()
    svc = SignalService(batch_size=4)
    svc.register("fig9", _graph())
    pol = CostBalancedPolicy(DSP_TARGET) if policy == "cost_balanced" \
        else policy
    sched = CoScheduler(eng, svc, policy=pol)

    rng = np.random.default_rng(seed)
    arrive_cycle: Dict[int, int] = {}
    done_cycle: Dict[int, int] = {}
    rid = 0
    lid = 0
    dsp_acc = llm_acc = 0.0
    for t in range(ticks):
        dsp_acc += dsp_per_tick
        while dsp_acc >= 1.0:
            dsp_acc -= 1.0
            length = int(rng.integers(FRAME, MAXLEN + 1))
            now = sched.llm_cycles + sched.dsp_cycles
            sched.submit_signal(SignalRequest(
                rid=rid, graph="fig9",
                samples=rng.standard_normal(length).astype(np.float32),
                deadline=now + 200_000.0))
            arrive_cycle[rid] = now
            rid += 1
        llm_acc += llm_per_tick
        while llm_acc >= 1.0:
            llm_acc -= 1.0
            now = sched.llm_cycles + sched.dsp_cycles
            sched.submit_llm(Request(
                rid=10_000_000 + lid, max_new=8,
                prompt=[1 + int(x) for x in rng.integers(1, 100, size=4)],
                deadline=now + 400_000.0))
            lid += 1
        sched.tick()
        now = sched.llm_cycles + sched.dsp_cycles
        for r in sched.dsp_results:
            done_cycle.setdefault(r, now)
    occ_loaded = sched.occupancy()             # split under sustained load
    while not sched.idle:                      # drain the backlog
        sched.tick()
        now = sched.llm_cycles + sched.dsp_cycles
        for r in sched.dsp_results:
            done_cycle.setdefault(r, now)

    lats = sorted(done_cycle[r] - arrive_cycle[r] for r in done_cycle)
    pct = (lambda p: float(lats[min(len(lats) - 1,
                                    int(p * len(lats)))]) if lats else 0.0)
    return {
        "policy": policy,
        "offered_dsp_per_tick": dsp_per_tick,
        "offered_llm_per_tick": llm_per_tick,
        "ticks_offered": ticks,
        "ticks_total": sched.ticks,
        "dsp_completed": len(done_cycle),
        "llm_completed": len(sched.llm_results),
        "p50_cycles": pct(0.50),
        "p95_cycles": pct(0.95),
        "dsp_share_loaded": occ_loaded["dsp_share"],
        "dsp_share_final": sched.occupancy()["dsp_share"],
        "llm_cycles": sched.llm_cycles,
        "dsp_cycles": sched.dsp_cycles,
    }, sched


def simulate_sessions(n_sessions: int, n_ticks: int,
                      chunk: int = 4 * HOP, seed: int = 1) -> Dict:
    """Lock-stepped streaming sessions: jitted core calls per tick must
    stay at 1 for same-graph sessions (batched chunk steps)."""
    from repro.serving import SignalService

    svc = SignalService(block_frames=4)
    svc.register("fig9", _graph())
    rng = np.random.default_rng(seed)
    sessions = [svc.open_stream("fig9") for _ in range(n_sessions)]
    calls: List[int] = []
    emitted = 0
    for _ in range(n_ticks):
        for s in sessions:
            s.feed(jnp.asarray(rng.standard_normal(chunk).astype(
                np.float32)))
        calls.append(svc.stream_step())
        empty = np.zeros(0, np.float32)
        for s in sessions:
            emitted += s.read().get("out", empty).shape[-1]
    for s in sessions:
        emitted += s.close().get("out", np.zeros(0, np.float32)).shape[-1]
    active = [c for c in calls if c]
    return {
        "sessions": n_sessions,
        "ticks": n_ticks,
        "core_calls": sum(calls),
        "max_calls_per_tick": max(calls) if calls else 0,
        "calls_per_active_tick": (sum(active) / len(active)) if active
        else 0.0,
        "samples_emitted": emitted,
    }


def simulate_sched(sched_on: bool, windows: int, seed: int = 3) -> Dict:
    """Mixed-deadline DSP offered load through the bare SignalService
    tick, SigSched on vs off on the IDENTICAL request sequence.

    Each window submits a burst of 8 loose (``deadline=inf``) requests
    near the top bucket, split across two fingerprint-equal graphs, then
    trickles 6 deadline-critical small requests while ticking — the
    scheduler-off FIFO head-of-line blocks every tight request behind
    the whole accumulated burst backlog; SigSched preempts with them
    (EDF), batches the twin graphs' bursts into one wave (cross-graph),
    and splits the bursts across ticks (``row_budget``) so tight
    newcomers interleave.  The latency clock is ``est_cycles``
    (perf-model cycles of executed work).  Total offered work is
    identical by construction, so throughput (requests per est-cycle)
    is equal on/off — only WHO waits changes, which is the point."""
    import math
    from repro.serving import SignalRequest, SignalService

    svc = SignalService(
        batch_size=8,
        scheduler={"row_budget": 2} if sched_on else False)
    svc.register("fig9a", _graph())
    svc.register("fig9b", _graph())
    rng = np.random.default_rng(seed)
    arrive: Dict[int, int] = {}
    done: Dict[int, int] = {}
    tight: set = set()
    rid = 0

    def submit(length: int, deadline: float, graph: str) -> None:
        nonlocal rid
        now = svc.est_cycles
        svc.submit(SignalRequest(
            rid=rid, graph=graph, deadline=deadline,
            samples=rng.standard_normal(length).astype(np.float32)))
        arrive[rid] = now
        if deadline < math.inf:
            tight.add(rid)
        rid += 1

    def tick() -> None:
        res = svc.step()
        now = svc.est_cycles
        for r in res:
            done.setdefault(r, now)

    for _ in range(windows):
        for j in range(8):
            submit(int(rng.integers(400, MAXLEN + 1)), math.inf,
                   "fig9a" if j % 2 else "fig9b")
        for j in range(6):
            submit(int(rng.integers(FRAME, 200)),
                   float(svc.est_cycles) + 1.0,
                   "fig9a" if j % 2 else "fig9b")
            tick()
    while svc.pending():
        tick()

    lat_t = sorted(done[r] - arrive[r] for r in done if r in tight)
    lat_all = sorted(done[r] - arrive[r] for r in done)

    def pct(xs, p):
        return float(xs[min(len(xs) - 1, int(p * len(xs)))]) if xs else 0.0

    rec = {
        "sched": "on" if sched_on else "off",
        "windows": windows,
        "completed": len(done),
        "deadline_bearing": len(lat_t),
        "p50_deadline_cycles": pct(lat_t, 0.50),
        "p95_deadline_cycles": pct(lat_t, 0.95),
        "p50_all_cycles": pct(lat_all, 0.50),
        "p95_all_cycles": pct(lat_all, 0.95),
        "est_cycles": svc.est_cycles,
        "batches": svc.stats["batches"],
    }
    if svc.scheduler is not None:
        s = svc.scheduler.stats
        rec.update(cross_graph_batches=s["cross_graph_batches"],
                   wave_splits=s["wave_splits"],
                   deferrals=s["deferrals"],
                   starvation_picks=s["starvation_picks"])
    return rec


SCHED_HEADER = ("sched,completed,deadline_bearing,p50_deadline,"
                "p95_deadline,p50_all,p95_all,batches,est_cycles")


def format_sched_row(r: Dict) -> str:
    return (f"{r['sched']},{r['completed']},{r['deadline_bearing']},"
            f"{r['p50_deadline_cycles']:.0f},{r['p95_deadline_cycles']:.0f},"
            f"{r['p50_all_cycles']:.0f},{r['p95_all_cycles']:.0f},"
            f"{r['batches']},{r['est_cycles']}")


def simulate_mesh(n_shards: int, n_requests: int = 24,
                  n_sessions: int = 4, n_ticks: int = 8,
                  seed: int = 2) -> Dict:
    """SigMesh drain: the identical workload (bucketed one-shot waves +
    lock-stepped stream sessions) through an unsharded service and an
    ``n_shards``-sharded one.  Latency clock = ``wall_cycles`` (the max
    per-device share per execution — the clock sharding improves; the
    offered-work clock ``est_cycles`` is invariant).  ``match`` is the
    bitwise sharded-vs-unsharded comparison of every result."""
    from repro.serving import SignalMesh, SignalRequest, SignalService

    rng = np.random.default_rng(seed)
    sigs = [rng.standard_normal(int(n)).astype(np.float32)
            for n in rng.integers(FRAME, MAXLEN + 1, size=n_requests)]
    chunk = 4 * HOP
    waves = [rng.standard_normal(n_ticks * chunk).astype(np.float32)
             for _ in range(n_sessions)]

    def drain(mesh):
        svc = SignalService(batch_size=8, block_frames=4, mesh=mesh)
        svc.register("fig9", _graph())
        lats: List[int] = []
        res: Dict[int, Dict] = {}
        for lo in range(0, n_requests, 8):
            for i, s in enumerate(sigs[lo:lo + 8]):
                svc.submit(SignalRequest(rid=lo + i, graph="fig9",
                                         samples=s))
            while svc.pending():
                before = svc.wall_cycles
                res.update(svc.step())
                lats.append(svc.wall_cycles - before)
        sessions = [svc.open_stream("fig9") for _ in range(n_sessions)]
        outs: List[List[np.ndarray]] = [[] for _ in sessions]
        empty = np.zeros(0, np.float32)
        for t in range(n_ticks):
            for s, w in zip(sessions, waves):
                s.feed(jnp.asarray(w[t * chunk:(t + 1) * chunk]))
            before = svc.wall_cycles
            svc.stream_step()
            lats.append(svc.wall_cycles - before)
            for o, s in zip(outs, sessions):
                o.append(s.read().get("out", empty))
        for o, s in zip(outs, sessions):
            o.append(s.close().get("out", empty))
        return (res, [np.concatenate(o, axis=-1) for o in outs],
                lats, svc)

    res0, outs0, _, _ = drain(None)
    res1, outs1, lats, svc = drain(SignalMesh(n_shards))
    match = (sorted(res0) == sorted(res1)
             and all(np.array_equal(res0[i]["out"], res1[i]["out"])
                     for i in res0)
             and all(np.array_equal(a, b)
                     for a, b in zip(outs0, outs1)))
    lats.sort()
    pct = (lambda p: float(lats[min(len(lats) - 1,
                                    int(p * len(lats)))]) if lats else 0.0)
    occ = svc.router.occupancy()
    return {
        "n_shards": n_shards,
        "devices": len(jax.devices()),
        "match": bool(match),
        "p50_wall_cycles": pct(0.50),
        "p95_wall_cycles": pct(0.95),
        "wall_cycles": svc.wall_cycles,
        "est_cycles": svc.est_cycles,
        "busy_devices": sum(1 for c in occ["device_cycles"] if c),
        "device_share": [round(s, 4) for s in occ["device_share"]],
    }


def run_mesh_sweep(shard_counts: List[int]) -> List[Dict]:
    """One subprocess per shard count with that many *forced host
    devices* (XLA_FLAGS must be set before jax imports, so the sweep
    cannot run in this process).  Each subprocess runs
    ``--mesh-inner N`` and prints its :func:`simulate_mesh` record as
    the last stdout line.

    The children run on forced CPU devices and report SigDLA model
    cycles, never a device measurement; on a TPU host that would pass
    CPU numbers off as the mesh result (and a child could not reach the
    chip this process holds), so the sweep refuses to run there."""
    import subprocess

    if jax.default_backend() != "cpu":
        raise RuntimeError(
            f"the --mesh sweep runs on forced CPU host devices; this host's "
            f"backend is {jax.default_backend()!r}, whose mesh it cannot "
            f"measure — run chip_smoke.py --mesh 4 on a four-chip host")
    root = os.path.join(os.path.dirname(__file__), "..")
    rows = []
    for n in shard_counts:
        env = dict(os.environ)
        env["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={max(1, n)}"
        env["PYTHONPATH"] = os.path.join(root, "src")
        env.setdefault("JAX_PLATFORMS", "cpu")
        out = subprocess.run(
            [sys.executable, "-m", "benchmarks.signal_service_bench",
             "--mesh-inner", str(n)],
            capture_output=True, text=True, timeout=600, env=env,
            cwd=root)
        if out.returncode != 0:
            raise SystemExit(f"mesh sweep subprocess (n={n}) failed:\n"
                             f"{out.stderr[-4000:]}")
        rows.append(json.loads(out.stdout.strip().splitlines()[-1]))
    return rows


MESH_HEADER = ("n_shards,devices,match,p50_wall_cycles,p95_wall_cycles,"
               "wall_cycles,est_cycles,busy_devices")


def format_mesh_row(r: Dict) -> str:
    return (f"{r['n_shards']},{r['devices']},{int(r['match'])},"
            f"{r['p50_wall_cycles']:.0f},{r['p95_wall_cycles']:.0f},"
            f"{r['wall_cycles']},{r['est_cycles']},{r['busy_devices']}")


LOAD_HEADER = ("policy,dsp_per_tick,llm_per_tick,dsp_done,llm_done,"
               "p50_cycles,p95_cycles,dsp_share_loaded,dsp_share_final")


def format_load_row(r: Dict) -> str:
    return (f"{r['policy']},{r['offered_dsp_per_tick']:g},"
            f"{r['offered_llm_per_tick']:g},{r['dsp_completed']},"
            f"{r['llm_completed']},{r['p50_cycles']:.0f},"
            f"{r['p95_cycles']:.0f},{r['dsp_share_loaded']:.3f},"
            f"{r['dsp_share_final']:.3f}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--ticks", type=int, default=600,
                    help="offered-load window (scheduler ticks)")
    ap.add_argument("--sessions", type=int, default=4)
    ap.add_argument("--session-ticks", type=int, default=12)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sweep for CI")
    ap.add_argument("--json", type=str, default=None,
                    help="also write a JSON summary to this path")
    ap.add_argument("--trace", type=str, default=None,
                    help="run under SigTrace and export a Chrome trace "
                         "to this path (REPRO_TRACE=1|<path> also works)")
    ap.add_argument("--mesh", type=str, default=None,
                    help="comma-separated shard counts to sweep in "
                         "forced-device subprocesses, e.g. --mesh 1,8 "
                         "(--smoke defaults to 1,8)")
    ap.add_argument("--sched", action="store_true",
                    help="mixed-deadline offered-load sweep, SigSched on "
                         "vs off (implied by --smoke)")
    ap.add_argument("--mesh-inner", type=int, default=None,
                    help=argparse.SUPPRESS)   # subprocess entry point
    args = ap.parse_args(argv)

    if args.mesh_inner is not None:
        # inside a run_mesh_sweep subprocess: one record, last line JSON
        print(json.dumps(simulate_mesh(args.mesh_inner)))
        return

    from repro import obs
    if args.trace:
        obs.enable(trace_path=args.trace)
    else:
        obs.enable_from_env()

    ticks = 120 if args.smoke else args.ticks
    # offered load (dsp, llm) requests per tick: a balanced point plus a
    # DSP-skewed point where round_robin's occupancy visibly drifts while
    # cost_balanced holds its target (the acceptance number).
    sweep = [(0.80, 0.20)] if args.smoke else [(0.15, 0.20), (0.80, 0.20)]

    load_rows = []
    last_sched = None
    print(LOAD_HEADER)
    for dsp_rate, llm_rate in sweep:
        for policy in POLICIES:
            r, sched = simulate(policy, ticks, dsp_rate, llm_rate)
            load_rows.append(r)
            if policy == "cost_balanced":
                last_sched = sched
            print(format_load_row(r))

    sess = simulate_sessions(args.sessions,
                             6 if args.smoke else args.session_ticks)
    print("\nsessions,ticks,core_calls,max_calls_per_tick,"
          "calls_per_active_tick")
    print(f"{sess['sessions']},{sess['ticks']},{sess['core_calls']},"
          f"{sess['max_calls_per_tick']},"
          f"{sess['calls_per_active_tick']:.2f}")
    if sess["max_calls_per_tick"] > 1:
        raise SystemExit("FAIL: same-graph sessions issued more than one "
                         "jitted core call in a tick")
    cb = [r for r in load_rows if r["policy"] == "cost_balanced"]
    worst = max(abs(r["dsp_share_loaded"] - DSP_TARGET) for r in cb)
    print(f"\ncost_balanced occupancy error vs target {DSP_TARGET}: "
          f"{worst:.3f}")
    if worst > 0.10:
        raise SystemExit("FAIL: cost_balanced occupancy split drifted "
                         ">10% from target under load")

    mesh_arg = args.mesh or ("1,8" if args.smoke else None)
    mesh_rows: List[Dict] = []
    if mesh_arg:
        mesh_rows = run_mesh_sweep(
            [int(n) for n in mesh_arg.split(",") if n.strip()])
        print("\n" + MESH_HEADER)
        for r in mesh_rows:
            print(format_mesh_row(r))
        if args.smoke and not all(r["match"] for r in mesh_rows):
            raise SystemExit("FAIL: sharded drain is not bit-identical "
                             "to the unsharded service")

    sched_rows: List[Dict] = []
    if args.sched or args.smoke:
        print("\n" + SCHED_HEADER)
        for on in (False, True):
            r = simulate_sched(on, windows=8 if args.smoke else 30)
            sched_rows.append(r)
            print(format_sched_row(r))
        off_r, on_r = sched_rows
        p_off, p_on = (off_r["p95_deadline_cycles"],
                       on_r["p95_deadline_cycles"])
        imp = 1.0 - p_on / p_off if p_off else 0.0
        print(f"\nsched p95 deadline latency improvement vs off: "
              f"{imp:.1%} (throughput {on_r['completed']}/{off_r['completed']}"
              f" requests in {on_r['est_cycles']}/{off_r['est_cycles']} "
              f"cycles)")
        if on_r["completed"] != off_r["completed"]:
            raise SystemExit("FAIL: sched on/off completed different "
                             "request counts")
        if abs(on_r["est_cycles"] - off_r["est_cycles"]) > \
                0.01 * off_r["est_cycles"]:
            raise SystemExit("FAIL: sched on/off throughput mismatch "
                             "(executed cycles diverged >1%)")
        if args.smoke and imp < 0.25:
            raise SystemExit("FAIL: SigSched improved deadline p95 by "
                             f"{imp:.1%} < 25% vs scheduler-off")

    report = None
    if obs.ENABLED:
        # post-run observability artifacts: the latency/occupancy report
        # (printed + embedded in --json) and the validated Chrome trace.
        report = obs.build_report(scheduler=last_sched,
                                  dsp_target=DSP_TARGET)
        print("\n" + obs.render_report(report))
        path = obs.get_tracer().export(obs.default_trace_path())
        stats = obs.validate_trace(path)
        print(f"\nwrote trace {path} ({stats['events']} events, "
              f"{len(stats['lanes'])} lanes)")

    if args.json:
        payload = {"schema_version": BENCH_SCHEMA_VERSION,
                   "load_sweep": load_rows, "streaming": sess,
                   "dsp_target": DSP_TARGET}
        if mesh_rows:
            payload["mesh_sweep"] = mesh_rows
        if sched_rows:
            payload["sched_sweep"] = sched_rows
        if report is not None:
            payload["report"] = report
        d = os.path.dirname(args.json)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"wrote {args.json}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
