"""Find the load a cell's traffic sustains: run the cell's traffic mode at
each value of one traffic parameter, in one process on one set-up, and
print each window's end-to-end metrics and backlog trend as a JSON line.

    python3 bench/sweep.py --config fig9-speech-enhance \\
        --traffic fig9-stream-sessions --param sessions --values 1 2 4 8 \\
        --seconds 8 --seed 7

A load is sustained where the backlog at the close stays near 0 and the
latency or lag of the window's second half is no larger than the first's.
"""

import json
import os
import sys
import time

T_PROCESS = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import drive, harness  # noqa: E402


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--param", required=True)
    ap.add_argument("--values", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    wl = {"name": "sweep", "config": args.config, "traffic": args.traffic,
          "chips": 1}
    try:
        harness.device_info(1)
    except harness.Refused as e:
        print(f"sweep: refused: {e}", file=sys.stderr)
        return 2
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    cfg, mod, traffic, _, svc = harness.build(wl, args.seed)
    counter = drive.CompileCounter()
    for v in args.values:
        value = int(v) if v == int(v) else v
        cell = drive.Cell(svc, cfg["name"], cfg, mod,
                          dict(traffic, **{args.param: value}), args.seed,
                          args.seconds, time.perf_counter(), False,
                          lambda: None, lambda: None, counter)
        rec = drive.MODES[traffic["mode"]](cell)
        rec.e2e.pop("setup_s")
        print(json.dumps({args.param: value, **rec.e2e, **rec.notes,
                          "attempted": rec.attempted, "failed": rec.failed}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
