"""Host-device transfer: milliseconds per wave in ``repro.wave.h2d``
(the stacked array to the device)."""

from bench import program_spans


def read(run):
    return program_spans.ms_per_wave(run, "repro.wave.h2d")
