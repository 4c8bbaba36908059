"""Service host path: milliseconds per wave in ``repro.wave.finish``
(perf-model accounting, stats, trimming each row's results)."""

from bench import program_spans


def read(run):
    return program_spans.ms_per_wave(run, "repro.wave.finish")
