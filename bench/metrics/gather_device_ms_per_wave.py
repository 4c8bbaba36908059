"""Shuffle fabric: device milliseconds per wave of the ops whose
``op_name`` lies under a ``gather:`` scope of the compiled program (the
XLA gathers ahead of the array kernels), mapped through the optimised
HLO text of each program the window ran."""

from bench import program_spans


def read(run):
    return program_spans.scoped_device_ms_per_wave(run)
