"""Kernels: the least time of every ``shuffle_gemm`` call the window's
waves made (the larger of operations over the bf16 peak and operand
plus result bytes over HBM bandwidth, from the call's shapes in the
compiled program's text) over the summed device time of the trace's ops
that are the kernel's calls (HLO instructions named
``%shuffle_gemm...``), in percent."""

from bench import kernels, program_spans
from bench import trace as tr

KERNEL = "shuffle_gemm"


def read(run):
    t0, t1 = tr.window(run.trace)
    dev_ns = sum(e.dur_ns for e in run.trace.ops()
                 if e.is_instruction(KERNEL) and t0 <= e.start_ns <= t1)
    if not dev_ns:
        return None
    calls = {key: kernels.parse_calls(text, KERNEL)
             for key, text in program_spans.program_texts(run).items()}
    least = 0.0
    for w in run.record.waves:
        for c in calls.get((w["bucket"], len(w["lens"])), []):
            least += max(c["flops"] / run.peaks["bf16_flops_per_s"],
                         c["bytes"] / run.peaks["hbm_bytes_per_s"])
    if not least:
        return None
    return 100.0 * least / (dev_ns / 1e9)
