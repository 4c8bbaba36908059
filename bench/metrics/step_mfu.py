"""Compiled program: the algorithmic operations of the requests executed
in the window, at their true lengths (the configuration's ``flops``),
over the summed ``step`` wall time times the chip's bf16 peak, in
percent."""


def read(run):
    waves = [w for w in run.record.waves if w["lens"]]
    busy = sum(w["t1"] - w["t0"] for w in waves)
    if not busy:
        return None
    ops = sum(run.mod.flops(run.cfg, n) for w in waves for n in w["lens"])
    return 100.0 * ops / (busy * run.peaks["bf16_flops_per_s"])
