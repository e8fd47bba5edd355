"""`trace_reduce` on a trace recorded on a TPU v5 lite: two serving
windows of chatglm3-6b's widths cut to 2 layers, 16 lanes, with the
harness's host spans (`bench/tests/data/small_trace.xplane.pb.gz`).
The pinned numbers were read from that trace once; the checks beside
them recompute the same quantities a second way."""
import gzip
from pathlib import Path

import pytest

from bench import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data" / "small_trace.xplane.pb.gz"


@pytest.fixture(scope="module")
def events():
    return tr.load(gzip.decompress(DATA.read_bytes()))


@pytest.fixture(scope="module")
def summary(events):
    return tr.reduce_events(*events)


def test_busy_and_idle(summary, events):
    ops, host = events
    assert summary.window_s == pytest.approx(PIN["window_s"], rel=1e-9)
    assert summary.busy_s == pytest.approx(PIN["busy_s"], rel=1e-9)
    assert 0 < summary.busy_s < summary.window_s
    # the idle gaps by host phase add up to the idle time
    idle = sum(v for _, v in summary.idle_gaps)
    assert idle == pytest.approx(summary.window_s - summary.busy_s, rel=1e-6)
    assert dict(summary.idle_gaps) == pytest.approx(PIN["idle_gaps"])


def test_paged_attention_kernel_time(summary, events):
    ops, host = events
    (w0, w1), = [(s, e) for n, s, e in host if n == tr.SPAN]
    by_hand = sum(min(e, w1) - max(s, w0) for n, s, e in ops
                  if n.startswith("paged_attention") and e > w0 and s < w1)
    assert summary.kernel_s["paged_attention"] == \
        pytest.approx(by_hand * 1e-9, rel=1e-12)
    assert summary.kernel_s["paged_attention"] == \
        pytest.approx(PIN["paged_attention_s"], rel=1e-9)
    assert summary.kernel_s["paged_attention"] < summary.busy_s


def test_top_ops_are_self_times(summary):
    assert [n for n, _ in summary.top_ops] == PIN["top_names"]
    assert [v for _, v in summary.top_ops] == \
        pytest.approx(PIN["top_s"], rel=1e-9)
    # self times never exceed the busy time, and nested ops do not count
    # twice: their sum over all ops is the time some op was running
    assert sum(v for _, v in summary.top_ops) <= summary.busy_s * (1 + 1e-9)


def test_self_times_by_hand():
    ops = [("while.1", 0, 100), ("a", 10, 30), ("b", 40, 90), ("c", 50, 60),
           ("d", 120, 130)]
    got = tr._self_times(ops)
    assert got == {"while.1": 100 - 20 - 50, "a": 20, "b": 50 - 10, "c": 10,
                   "d": 10}


def test_gaps_go_to_the_innermost_host_span():
    ops = [("x", 0, 10), ("y", 20, 30), ("z", 50, 60)]
    host = [(tr.SPAN, 0, 70), ("dispatch", 12, 18), ("events", 32, 48),
            ("gauge_rss", 35, 45)]
    s = tr.reduce_events(ops, host)
    assert dict(s.idle_gaps) == pytest.approx(
        {"dispatch": 10e-9, "gauge_rss": 20e-9, tr.OTHER_HOST: 10e-9})
    assert s.busy_s == pytest.approx(30e-9)


def test_refuses_a_trace_without_device_ops():
    with pytest.raises(RuntimeError, match="no device op"):
        tr.reduce_events([], [(tr.SPAN, 0, 10)])


# read from the trace once (TPU v5 lite, one chip)
PIN = {
    "window_s": 0.169988687,
    "busy_s": 0.12386013600000001,
    "paged_attention_s": 0.053269477,
    "idle_gaps": {"events": 0.018894963999999986,
                  "serve_loop": 0.015166990000000005,
                  "gauge_rss": 0.006061546, "gauge_live": 0.0036369490000000004,
                  "dispatch": 0.0023681019999999935},
    "top_names": ["paged_attention.24", "fusion.907", "bitcast_add_fusion.7",
                  "fusion.1037", "fusion.1038", "paged_attention.23",
                  "fusion.901", "fusion.1036", "while.415",
                  "bitcast_add_fusion.6"],
    "top_s": [0.046610449000000005, 0.019916756, 0.008395509, 0.00838323,
              0.008381397, 0.006659028, 0.0028203520000000004,
              0.0025697420000000003, 0.002210341, 0.0011995130000000001],
}
