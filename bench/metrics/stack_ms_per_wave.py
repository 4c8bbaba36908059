"""Service host path: milliseconds per wave in ``repro.wave.stack``
(claiming rows from the queue, params classes, padding and stacking the
wave's array)."""

from bench import program_spans


def read(run):
    return program_spans.ms_per_wave(run, "repro.wave.stack")
