"""The readings a cell's limits are set from: for each seed, one short
window of the cell's own traffic at its own size, then the numbers the
run compares, read over the same answers — for the program, for the
control (the plain reference computed one precision lower, put in the
program's place) and for the control with the model alone lowered.  For
the first ``--faults`` seeds, each of the configuration's planted faults
(``FAULTS``: the program served with broken weights) gets a window of its
own.  One JSON line per seed, in one process.

    python3 bench/control.py --workload dcase-offline --seconds 4 \\
        --seeds 11 12 13 --faults 3

Not part of a benchmark run; the lower reading of a limit is the largest
program reading over a dozen seeds or more, the upper the smallest
control reading (see PERF.md).
"""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import drive, harness  # noqa: E402


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--faults", type=int, default=0,
                    help="seeds, from the first, that also read each "
                         "planted fault")
    args = ap.parse_args(argv)
    wl = harness.workload(harness.load_benchmark(), args.workload)
    try:
        harness.device_info(wl["chips"])
    except harness.Refused as e:
        print(f"control: refused: {e}", file=sys.stderr)
        return 2
    import jax
    import numpy as np
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    counter = drive.CompileCounter()

    def window(seed, fault=None):
        cfg, mod, traffic, params, svc = harness.build(wl, seed,
                                                       fault=fault)
        cell = drive.Cell(svc, cfg["name"], cfg, mod, traffic, seed,
                          args.seconds, time.perf_counter(), False,
                          lambda: None, lambda: None, counter)
        rec = drive.MODES[traffic["mode"]](cell)
        host = jax.tree_util.tree_map(np.asarray, params)
        return cfg, mod, host, rec

    def values(checks):
        return {k: c["value"] for k, c in checks.items() if k != "failed"}

    for n, seed in enumerate(args.seeds):
        cfg, mod, host, rec = window(seed)
        prog = harness.check(cfg, mod, host, rec.answers, rec.failed)
        line = {"seed": seed, "answers": len(rec.answers),
                "program": {k: c["value"] for k, c in prog.items()},
                "control": values(harness.check(cfg, mod, host, rec.answers,
                                                0, control=True)),
                "control_model": values(harness.check(
                    cfg, mod, host, rec.answers, 0, control="model"))}
        line.update(rec.e2e)
        for fault in getattr(mod, "FAULTS", {}) if n < args.faults else ():
            cfg, mod, host, frec = window(seed, fault)
            line[fault] = values(harness.check(cfg, mod, host, frec.answers,
                                               frec.failed))
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
