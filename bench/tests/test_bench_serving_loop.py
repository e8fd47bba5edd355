"""The serving loop's readers of the program's own records: each on a
hand-made readout, silent on records that lack the program's stamps and
counters, and on the CPU smoke run against the harness's own timing of
the same dispatches."""
import statistics

import numpy as np
import pytest

from bench import harness, peaks, traffic
from bench.tests.test_bench_run import run, smoke_cell

HOST_GAP = "serving_loop.host_gap_ms_median"
QUEUE_WAIT = "serving_loop.queue_wait_p95_s"
USEFUL = "serving_loop.useful_lane_steps_pct"


class C:                                     # a Completion's fields
    def __init__(self, tokens, windows, t_admitted):
        self.tokens, self.windows = tokens, windows
        self.t_admitted = t_admitted


def _job(t0, ready, dispatch, useful, admitted):
    """A job whose serve_log holds one window per entry of `dispatch`;
    `admitted` gives each request's admitting window."""
    log = [{"t_dispatch": d, "t_ready": r, "t_tokens": r,
            "lane_steps": 64, "useful_lane_steps": u}
           for d, r, u in zip(dispatch, ready, useful)]
    done = [C([1], (a, a + 1), dispatch[a]) for a in admitted]
    specs = [traffic.Spec([1], 1) for _ in admitted]
    return harness.Job(specs, t0, t0 + 10.0, done, list(dispatch), log, [])


def _readout(jobs):
    return harness.Readout(None, None, peaks.lookup("TPU v5 lite"), 16,
                           jobs, None, None)


def test_readers_by_hand():
    a = _job(10.0, ready=[10.6, 11.7, 12.8], dispatch=[10.1, 10.7, 11.9],
             useful=[64, 32, 0], admitted=[0, 0, 1])
    b = _job(20.0, ready=[20.5, 21.6], dispatch=[20.1, 20.54],
             useful=[16, 16], admitted=[0, 1])
    r = _readout([a, b])
    # gaps: a 0.1 and 0.2 (its last window has none), b 0.04
    assert harness.reader(HOST_GAP)(r) == pytest.approx(100.0)
    # waits 0.1, 0.1, 0.7, 0.1, 0.54: linear rank 0.95 * 4 = 3.8
    assert harness.reader(QUEUE_WAIT)(r) == pytest.approx(
        0.54 + 0.8 * (0.7 - 0.54))
    # a ratio of sums over all five windows, not a mean of job shares
    assert harness.reader(USEFUL)(r) == pytest.approx(
        100.0 * 128 / (5 * 64))


def test_queue_wait_leaves_out_the_harness_time_in_dispatches():
    # the harness stamps window 1's dispatch 2 s after the program did
    # (a profiler start): requests admitted after it wait 2 s less
    a = _job(10.0, ready=[10.6, 13.7, 14.8], dispatch=[10.1, 10.7, 13.9],
             useful=[64, 32, 0], admitted=[0, 1, 2, 2, 2])
    a.dispatch_t[1] += 2.0
    waits = [0.1, 0.7, 1.9, 1.9, 1.9]
    assert harness.reader(QUEUE_WAIT)(_readout([a])) == pytest.approx(
        float(np.percentile(waits, 95)))


def test_readers_silent_without_the_programs_records():
    # a program without the stamps and counters: completions with only
    # tokens and windows, serve_log entries with only the gauges
    a = _job(10.0, ready=[10.6], dispatch=[10.1], useful=[64],
             admitted=[0])
    a.serve_log = [{"rss_bytes": 4.0, "live_bytes": 2.0}]
    for c in a.done:
        del c.t_admitted
    r = _readout([a])
    for name in (HOST_GAP, QUEUE_WAIT, USEFUL):
        assert harness.reader(name)(r) is None
    assert harness.reader(HOST_GAP)(_readout([_job(
        0.0, ready=[0.5], dispatch=[0.1], useful=[1], admitted=[0])])) \
        is None                               # one window: no gap


@pytest.fixture(scope="module")
def smoke_jobs():
    res = run(smoke_cell())
    assert res["correct"], res["checks"]
    return res["jobs"]


def test_serve_log_dispatch_matches_the_harness_stamp(smoke_jobs):
    for j in smoke_jobs:
        mine = [e["t_dispatch"] for e in j.serve_log]
        assert len(mine) == len(j.dispatch_t)
        assert max(abs(a - b) for a, b in zip(mine, j.dispatch_t)) < 5e-3
        steps = [(b - a) for a, b in zip(mine, mine[1:])]
        assert statistics.median(steps) == pytest.approx(
            statistics.median(j.intervals()), rel=0.01)


def test_useful_share_matches_the_harness_reconstruction(smoke_jobs):
    # the program counts in its lane loop what the harness rebuilds from
    # each completion's window span
    useful = sum(u for j in smoke_jobs for _, _, u in j.lane_steps())
    lane_steps = sum(len(j.serve_log) for j in smoke_jobs) * \
        smoke_cell().mix["lanes"] * harness.WINDOW
    r = _readout(smoke_jobs)
    assert harness.reader(USEFUL)(r) == pytest.approx(
        100.0 * useful / lane_steps)
    assert 0 < harness.reader(QUEUE_WAIT)(r) < \
        max(j.seconds for j in smoke_jobs)
    assert harness.reader(HOST_GAP)(r) > 0
