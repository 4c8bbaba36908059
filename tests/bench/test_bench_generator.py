"""The traffic generator: every seed gets the same work in another order."""

import numpy as np
import pytest

from bench import generator, harness


@pytest.fixture(scope="module")
def fig9():
    return harness.load_config("fig9-speech-enhance")


def test_open_loop_schedule_is_seeded_and_fills_the_window(fig9):
    cfg, mod = fig9
    tr = harness.load_traffic("fig9-oneshot-poisson")
    tr = dict(tr, rate_per_s=40)
    a = generator.open_loop(tr, 2 ** 31 + 7, 5.0, 200_000)
    b = generator.open_loop(tr, 2 ** 31 + 7, 5.0, 200_000)
    c = generator.open_loop(tr, 11, 5.0, 200_000)
    assert [(r.due, r.offset, r.length) for r in a] \
        == [(r.due, r.offset, r.length) for r in b]
    assert len(a) == len(c) == 200
    due = np.array([r.due for r in a])
    assert due[0] == 0.0 and np.all(np.diff(due) > 0) and due[-1] < 5.0
    # the same lengths and gaps for every seed, in another order
    assert sorted(r.length for r in a) == sorted(r.length for r in c)
    assert [r.length for r in a] != [r.length for r in c]
    gaps = lambda rs: np.sort(np.diff([r.due for r in rs] + [5.0]))
    assert abs(gaps(a).sum() - gaps(c).sum()) < 1e-9
    lens = np.array([r.length for r in a])
    assert lens.min() >= 16_000 and lens.max() <= 65_536
    assert abs(np.median(lens) - 32_000) < 1_000
    assert all(0 <= r.offset <= 200_000 - r.length for r in a)


def test_lognormal_lengths_are_clipped_quantiles():
    spec = {"dist": "lognormal", "median": 100, "sigma": 1.0,
            "min": 50, "max": 300}
    x = generator.lengths(spec, 1000)
    assert x.min() == 50 and x.max() == 300
    assert np.all(np.diff(x) >= 0)
    assert generator.lengths({"dist": "fixed", "value": 7}, 3).tolist() \
        == [7, 7, 7]


def test_backlog_clips_repeat_per_seed():
    tr = harness.load_traffic("dcase-backlog")
    a, b = (generator.Backlog(tr, 5, 400_000) for _ in range(2))
    ca = [a.take() for _ in range(4)]
    cb = [b.take() for _ in range(4)]
    assert [(c.idx, c.offset, c.length) for c in ca] \
        == [(c.idx, c.offset, c.length) for c in cb]
    assert all(c.length == 160_000 for c in ca)
    assert all(0 <= c.offset <= 240_000 for c in ca)


def test_stream_sessions_are_staggered_evenly(fig9):
    cfg, mod = fig9
    tr = dict(harness.load_traffic("fig9-stream-sessions"), sessions=4)
    s = generator.streams(mod, cfg, tr, 3, 1.0)
    period = tr["period_s"]
    assert [x.phase for x in s] == pytest.approx(
        [k * period / 4 for k in range(4)])
    need = (int(np.ceil(1.0 / period)) + 1) * tr["chunk"]
    assert all(len(x.audio) == need for x in s)
    again = generator.streams(mod, cfg, tr, 3, 1.0)
    assert all(np.array_equal(x.audio, y.audio) for x, y in zip(s, again))


class _Service:
    """Answers every queued request at once, up to ``batch_size`` a
    wave, and records each wave's rows and lengths."""

    def __init__(self, batch_size):
        self.batch_size = batch_size
        self.queue = []
        self.waves = []

    def submit(self, req):
        self.queue.append(req)

    def pending(self):
        return len(self.queue)

    def step(self):
        wave, self.queue = (self.queue[:self.batch_size],
                            self.queue[self.batch_size:])
        self.waves.append([(r.rid, len(r.samples)) for r in wave])
        return {r.rid: {} for r in wave}


def test_fixed_length_open_loop_warms_only_its_bucket():
    from bench import drive
    cfg, mod = harness.load_config("dcase2020-t2-ae")
    cfg = dict(cfg, service={"buckets": [4096, 8192, 16384]})
    tr = {"mode": "open_loop", "rate_per_s": 10, "pool_seconds": 2,
          "check_sample": 2,
          "length": {"dist": "fixed", "value": 8192, "min": 8192,
                     "max": 8192}}
    svc = _Service(batch_size=3)
    cell = drive.Cell(svc, cfg["name"], cfg, mod, tr, 2 ** 31 + 9, 0.3,
                      0.0, False, lambda: None, lambda: None,
                      drive.CompileCounter())
    rec = drive.open_loop(cell)
    warm = [w for w in svc.waves if w and w[0][0] < 0]
    # one wave of each row count, every row at the one bucket's length
    assert [len(w) for w in warm] == [1, 2, 3]
    assert {n for w in warm for _, n in w} == {8192}
    assert rec.attempted == 3 and rec.failed == 0
    assert all(w["bucket"] == 8192 for w in rec.waves if w["lens"])
