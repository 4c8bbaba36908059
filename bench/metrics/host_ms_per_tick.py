"""Streams: host-only milliseconds per tick, the ``bench.feed``,
``bench.stream_step`` and ``bench.read`` spans of each tick less the
device busy time inside them."""

from bench.metrics import host_ms_per_span


def read(run):
    return host_ms_per_span(
        run, {"bench.feed", "bench.stream_step", "bench.read"},
        "bench.stream_step")
