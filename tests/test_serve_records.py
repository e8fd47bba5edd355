"""What `Server.serve` records about itself: the per-window `serve_log`
stamps and host phases, the per-request `Completion` stamps, the KV
gauges the window program returns, the profiler spans that mirror the
stamps, and the named scopes of the compiled window.

All stamps are `time.perf_counter()` seconds; the profiler's spans of the
same phases sit at one constant offset from them."""
import glob
import os
import re

import jax
import numpy as np
import pytest

from repro.models.model import build
from repro.runtime.server import Request, Server, ServerConfig

B, W = 2, 4
KW = dict(batch=B, max_len=32, block_tokens=4, collect_every=W, window=W)
PHASES = ("schedule", "inputs", "dispatch", "reports", "tokens", "lanes",
          "log")
SCOPES = ("qkv", "kv_append", "attention", "ffn", "record", "logits",
          "sample", "lane_events", "collect", "migrate", "backend")

_MODEL = []
_SERVERS = {}
_HLO = []


def _model():
    if not _MODEL:
        m = build("chatglm3-6b", reduced=True)
        _MODEL.append((m, m.init(jax.random.PRNGKey(0))))
    return _MODEL[0]


def _server(backend="proactive"):
    """One compiled server per backend, shared by the tests. The
    reactive backend at its default HBM target of 0 bytes demotes every
    resident superblock at each collect."""
    if backend not in _SERVERS:
        m, _ = _model()
        _SERVERS[backend] = Server(m, ServerConfig(backend=backend, **KW))
    return _SERVERS[backend]


def _requests():
    """More requests than lanes, with prompts longer than a window, so
    lanes finish and refill and first tokens arrive after admission."""
    rng = np.random.default_rng(5)
    sizes = [(3, 5), (6, 9), (2, 3), (9, 4), (5, 7)]
    return [Request(prompt=rng.integers(0, 256, (p,)).tolist(), max_new=n)
            for p, n in sizes]


def _serve(srv):
    _, params = _model()
    return srv.serve(params, _requests())


def test_window_stamps_are_ordered():
    srv = _server()
    _serve(srv)
    log = srv.serve_log
    assert len(log) > 3
    for k, e in enumerate(log):
        assert e["t_dispatch"] < e["t_ready"] <= e["t_tokens"]
        if k + 1 < len(log):
            assert e["t_tokens"] <= log[k + 1]["t_dispatch"]
        assert set(e["host_ms"]) == set(PHASES)
        assert e["lane_steps"] == B * W
        assert 0 <= e["useful_lane_steps"] <= e["lane_steps"]


def test_completion_stamps_agree_with_windows():
    srv = _server()
    done = _serve(srv)
    log = srv.serve_log
    for c, r in zip(done, _requests()):
        a, f = c.windows
        assert c.t_admitted == log[a]["t_dispatch"]
        # the first generated token is sampled at the lane's step p - 1
        first = a + (len(r.prompt) - 1) // W
        assert c.t_first_token == log[first]["t_tokens"]
        assert c.t_finished == log[f - 1]["t_tokens"]
        assert c.t_admitted < c.t_first_token <= c.t_finished


def test_useful_lane_steps_count_prompt_and_kept_tokens():
    srv = _server()
    done = _serve(srv)
    need = sum(len(r.prompt) + len(c.tokens) - 1
               for c, r in zip(done, _requests()))
    assert sum(e["useful_lane_steps"] for e in srv.serve_log) == need
    assert sum(e["lane_steps"] for e in srv.serve_log) == \
        len(srv.serve_log) * B * W


def test_host_phases_fit_between_dispatches():
    """The phases after window k's dispatch starts and those before
    window k + 1's are disjoint stretches between the two dispatches."""
    srv = _server()
    _serve(srv)
    log = srv.serve_log
    for a, b in zip(log, log[1:]):
        after = sum(a["host_ms"][p] for p in PHASES[2:])
        before = b["host_ms"]["schedule"] + b["host_ms"]["inputs"]
        assert after + before <= 1e3 * (b["t_dispatch"] - a["t_dispatch"])


@pytest.mark.parametrize("backend", ["proactive", "reactive", "mglru"])
def test_in_window_gauges_equal_the_methods(backend):
    srv = _server(backend)
    after = []
    uncarry = srv._uncarry

    def record(carry):
        uncarry(carry)
        after.append((srv.kv_rss_bytes(), srv.kv_live_bytes()))
    srv._uncarry = record
    try:
        _serve(srv)
    finally:
        del srv._uncarry
    got = [(e["rss_bytes"], e["live_bytes"]) for e in srv.serve_log]
    assert got == after
    assert max(live for _, live in got) > 0 and got[-1] == (0.0, 0.0)
    if backend == "reactive":     # demoted superblocks leave the RSS
        assert any(rss < live for rss, live in got)
    else:
        assert all(rss >= live for rss, live in got)


def test_one_dispatch_per_window_and_no_gauge_reads():
    srv = _server()
    calls = []
    win = srv._win_serve

    def count(*a, **k):
        calls.append(1)
        return win(*a, **k)

    def refuse():
        raise AssertionError("serve read a gauge outside the window")
    srv._win_serve = count
    srv.kv_rss_bytes = srv.kv_live_bytes = refuse
    try:
        _serve(srv)
    finally:
        srv._win_serve = win
        del srv.kv_rss_bytes, srv.kv_live_bytes
    assert len(calls) == srv.dispatches == len(srv.serve_log)


def _host_events(trace_dir):
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    with open(path, "rb") as f:
        space = ProfileData.from_serialized_xspace(f.read())
    out = {}
    for plane in space.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("serve."):
                        out.setdefault(e.name, []).append(
                            (e.start_ns, e.end_ns))
    return {k: sorted(v) for k, v in out.items()}


def test_profiler_spans_sit_at_one_offset_from_serve_log(tmp_path):
    srv = _server()
    _serve(srv)                                  # compiled before tracing
    jax.profiler.start_trace(str(tmp_path))
    try:
        _serve(srv)
    finally:
        jax.profiler.stop_trace()
    ev = _host_events(str(tmp_path))
    log = srv.serve_log
    assert {f"serve.{p}" for p in PHASES} <= set(ev)
    assert len(ev["serve.window"]) == len(log) + 1   # + the drained check
    assert len(ev["serve.dispatch"]) == len(log)
    offsets = [s * 1e-9 - e["t_dispatch"]
               for (s, _), e in zip(ev["serve.dispatch"], log)]
    offsets += [t * 1e-9 - e["t_tokens"]
                for (_, t), e in zip(ev["serve.tokens"], log)]
    assert max(offsets) - min(offsets) <= 2e-3       # one offset, +-1 ms
    for (s, t), e in zip(ev["serve.dispatch"], log):
        assert (t - s) * 1e-6 == pytest.approx(e["host_ms"]["dispatch"],
                                               abs=1.0)


def _window_hlo():
    """The compiled serve window's HLO text, compiled once."""
    if not _HLO:
        _, params = _model()
        _HLO.append(_server().lower_serve_window(params).compile()
                    .as_text())
    return _HLO[0]


def test_window_program_carries_the_named_scopes():
    names = re.findall(r'op_name="([^"]*)"', _window_hlo())
    for scope in SCOPES:
        assert any(f"/{scope}/" in n for n in names), scope


def test_layer_loop_scatters_only_to_append():
    """Access bits are recorded once a step, after the layer scans
    (`record`): the layers' `attention` scatters nothing but the KV
    append's slot and block-table writes."""
    scatters = re.findall(r' scatter\(.*op_name="([^"]*)"', _window_hlo())
    attention = [n for n in scatters if "/attention/" in n]
    assert attention, "no scatter of the KV append in the layer loop"
    assert all("/attention/kv_append/" in n for n in attention), attention
