"""SigSched: milliseconds per wave of the dispatch span's own time —
``repro.sched.dispatch`` less the ``repro.wave`` inside it (group
collection, choice, split bookkeeping)."""

from bench import program_spans


def read(run):
    return program_spans.self_ms_per_wave(run, "repro.sched.dispatch",
                                          "repro.wave")
