"""Service host path: host-only milliseconds per executed wave, the
``bench.step`` span around each ``SignalService.step`` less the device
busy time inside it."""

from bench.metrics import host_ms_per_span


def read(run):
    return host_ms_per_span(run, {"bench.step"}, "bench.step")
