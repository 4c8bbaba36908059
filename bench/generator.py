"""The one traffic generator.  A traffic mix is a JSON file of parameters
under ``bench/traffic/``; this module turns it, a configuration and a
seed into the work of one run.

Every seed gets the same work in another order: request lengths and
inter-arrival gaps are fixed quantiles of the mix's distributions,
permuted by the seed; only the order and the audio change.

Modes (the mix's ``mode``):

* ``open_loop``: one-shot requests due at fixed times (Poisson gaps at
  ``rate_per_s``), lengths from ``length``; every request is due inside
  the window.
* ``backlog``: an offline queue of clips of ``length`` kept at least
  ``min_waves`` service waves deep.
* ``streams``: ``sessions`` real-time sessions, each fed ``chunk``
  samples every ``period_s`` seconds, phases staggered evenly.
"""

from __future__ import annotations

import dataclasses
import statistics
from typing import List

import numpy as np

from bench import arith


def _quantiles(n):
    return (np.arange(n) + 0.5) / n


def lengths(spec, n):
    """``n`` request lengths: fixed quantiles of the mix's ``length``
    distribution (``fixed`` or clipped ``lognormal``), sorted."""
    if spec["dist"] == "fixed":
        return np.full(n, int(spec["value"]), np.int64)
    if spec["dist"] == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(q)
                      for q in _quantiles(n)])
        x = spec["median"] * np.exp(spec["sigma"] * z)
        return np.clip(np.round(x), spec["min"], spec["max"]).astype(np.int64)
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def poisson_due(rate, seconds, rng):
    """Due times in [0, seconds) of ``round(rate * seconds)`` requests:
    exponential gaps at fixed quantiles, in the seed's order, scaled so
    that they fill the window exactly."""
    n = max(1, int(round(rate * seconds)))
    gaps = -np.log1p(-_quantiles(n)) / rate
    gaps = rng.permutation(gaps)
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return due * (seconds / gaps.sum())


@dataclasses.dataclass
class Request:
    idx: int
    due: float          # seconds after the window opens
    offset: int         # start in the audio pool
    length: int


@dataclasses.dataclass
class Session:
    idx: int
    phase: float        # seconds after the window opens of chunk 0
    audio: np.ndarray   # everything the session will be fed


def audio_pool(mod, cfg, traffic, seed):
    seconds = traffic.get("pool_seconds", 64)
    return mod.make_audio(cfg, arith.np_rng(seed, 1),
                          int(seconds * cfg["sample_rate"]))


def open_loop(traffic, seed, seconds, pool_len) -> List[Request]:
    rng = arith.np_rng(seed, 2)
    due = poisson_due(traffic["rate_per_s"], seconds, rng)
    lens = rng.permutation(lengths(traffic["length"], len(due)))
    offs = rng.integers(0, pool_len - lens + 1)
    return [Request(i, float(t), int(o), int(n))
            for i, (t, o, n) in enumerate(zip(due, offs, lens))]


class Backlog:
    """An endless queue of clips; clip ``i`` is the same for a seed."""

    def __init__(self, traffic, seed, pool_len):
        self.length = int(lengths(traffic["length"], 1)[0])
        self.pool_len = pool_len
        self.seed = seed
        self.next = 0

    def take(self) -> Request:
        i = self.next
        self.next += 1
        off = int(arith.np_rng(self.seed, 3, i).integers(
            0, self.pool_len - self.length + 1))
        return Request(i, 0.0, off, self.length)


def streams(mod, cfg, traffic, seed, seconds) -> List[Session]:
    """Sessions with enough audio for the window plus one period."""
    n = traffic["sessions"]
    period = traffic["period_s"]
    chunks = int(np.ceil(seconds / period)) + 1
    rng = arith.np_rng(seed, 4)
    return [Session(k, k * period / n,
                    mod.make_audio(cfg, rng, chunks * traffic["chunk"]))
            for k in range(n)]
