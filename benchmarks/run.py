"""Benchmark driver: ``PYTHONPATH=src python -m benchmarks.run``.

Prints name,value,paper,unit CSV for every paper table/figure
(paper-claims reproduction), the kernel wall-time microbenches, and — when
dry-run artifacts exist — the §Roofline summary table.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main() -> None:
    from benchmarks import kernel_bench, paper_claims

    print("name,ours,paper,unit")
    for name, ours, paper, unit in paper_claims.all_rows():
        print(f"{name},{ours:.4g},{paper:.4g},{unit}")

    print("\nname,us_per_call,derived")
    for name, us, derived in kernel_bench.rows():
        print(f"{name},{us:.1f},{derived}")

    from benchmarks import signal_graph_bench
    print("\n" + signal_graph_bench.HEADER)
    for row in signal_graph_bench.rows():
        print(signal_graph_bench.format_row(row))

    art = os.path.join(os.path.dirname(__file__), "..", "artifacts",
                       "dryrun")
    if os.path.isdir(art) and any(f.endswith(".json")
                                  for f in os.listdir(art)):
        print("\n== roofline (single-pod 16x16; see EXPERIMENTS.md) ==")
        from benchmarks import roofline
        roofline.main()
    else:
        print("\n(no dry-run artifacts; run scripts/run_dryrun_sweep.sh "
              "for the roofline table)")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
