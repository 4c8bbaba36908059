"""Host-device transfer: milliseconds per wave in ``repro.wave.fetch``
(the wait for the device and the copy of the results back) less the
device busy time inside it."""

from bench import program_spans


def read(run):
    return program_spans.host_ms_per_wave(run, "repro.wave.fetch")
