"""The benchmark's arithmetic against hand sums: end-to-end metrics from a
hand-made job, the per-layer counters, the FLOP and byte counts at
chatglm3-6b's shapes, a family's costs found by name, the peak table and
the traffic generator."""
import dataclasses
import json
import re
import sys
import types
from pathlib import Path

import numpy as np
import pytest

from bench import check, harness, peaks, traffic
from bench.models import glm_costs as costs
from bench.models import glm_reference as ref

BENCH = Path(__file__).resolve().parents[1]
CHATGLM = ref.shapes(json.loads(
    (BENCH / "configs" / "chatglm3-6b.json").read_text()))


class C:                                     # a Completion's fields
    def __init__(self, tokens, windows):
        self.tokens, self.windows = tokens, windows


def _job(t0=100.0):
    """Three requests on a 16-step window: admitted at windows 0, 0, 1 and
    freed at the entries of windows 2, 1, 3; the job ran four dispatches
    and returned at t0 + 5."""
    specs = [traffic.Spec([1] * 10, 20), traffic.Spec([2] * 3, 5),
             traffic.Spec([3] * 4, 20)]
    done = [C([7] * 20, (0, 2)), C([8] * 5, (0, 1)), C([9] * 20, (1, 3))]
    log = [{"rss_bytes": 4.0, "live_bytes": 2.0},
           {"rss_bytes": 6.0, "live_bytes": 4.0},
           {"rss_bytes": 2.0, "live_bytes": 2.0},
           {"rss_bytes": 0.0, "live_bytes": 0.0}]
    reports = [{"moved_to_hot": 3, "moved_to_cold": 1, "win_faults": 0,
                "win_accesses": 900},
               {"moved_to_hot": 0, "moved_to_cold": 2, "win_faults": 3,
                "win_accesses": 1100}]
    return harness.Job(specs, t0, t0 + 5.0, done,
                       [t0 + 0.5, t0 + 1.5, t0 + 2.5, t0 + 3.5], log, reports)


def _readout(jobs, trace=None, traced=None):
    return harness.Readout(CHATGLM, costs, peaks.lookup("TPU v5 lite"), 16,
                           jobs, trace, traced)


def test_latencies_read_the_freeing_dispatch():
    # freed at the entry of window f: the host had the last token when
    # dispatch f was issued
    assert _job().latencies() == pytest.approx([2.5, 1.5, 3.5])


def test_end_to_end_metrics_by_hand():
    a, b = _job(100.0), _job(105.5)          # 0.5 s between the jobs
    e2e = harness.end_to_end([a, b], setup_s=12.5)
    assert e2e["out_tok_per_s"] == pytest.approx(2 * 45 / 10.5)
    # 6 samples; numpy's linear interpolation at rank 0.95 * 5 = 4.75
    lat = sorted([2.5, 1.5, 3.5] * 2)
    assert e2e["req_latency_p95_s"] == pytest.approx(
        lat[4] + 0.75 * (lat[5] - lat[4]))
    assert e2e["setup_s"] == 12.5


def test_p95_sample_count():
    # 20 requests: the 95th percentile lies between the 19th and 20th
    job = _job()
    job.dispatch_t = [job.t0 + i for i in range(21)]
    job.done = [C([0], (0, i + 1)) for i in range(20)]
    e2e = harness.end_to_end([job], 0.0)
    assert e2e["req_latency_p95_s"] == pytest.approx(19.05)


def test_counters_are_ratios_of_sums():
    # moves summed over both directions and every collect of both jobs,
    # over the number of collects: not a mean of per-job means
    a, b = _job(), _job()
    b.reports = b.reports[:1]
    r = _readout([a, b])
    assert harness.reader("collector.moves_per_window")(r) == \
        pytest.approx((6 + 4) / 3)
    assert harness.reader("collector.moves_per_window")(
        _readout([harness.Job([], 0.0, 1.0, [], [], [], [])])) is None


def test_step_median_ignores_one_stalled_window():
    a, b = _job(), _job()
    # a's windows take 1.0 s each; b's second window stalls for 3 s
    b.dispatch_t = [b.t0 + 0.5, b.t0 + 1.5, b.t0 + 5.5, b.t0 + 6.5]
    assert b.intervals() == pytest.approx([1.0, 4.0, 1.0])
    r = _readout([a, b])
    assert harness.reader("serving_loop.step_ms_median")(r) == \
        pytest.approx(1000.0 / 16)
    assert harness.reader("serving_loop.step_ms_median")(
        _readout([harness.Job([], 0.0, 1.0, [], [0.5], [], [])])) is None


@pytest.mark.parametrize("seconds,stall,count", [
    (45.0, 0.0, 2),       # a job of 22.5 s, half the window: two jobs
    (45.0, 0.1, 2),       # 22.6 s: two, where a floor would flip to one
    (45.0, 3.0, 2),       # a job stretched to 25.5 s by one stall
    (44.0, 0.0, 2),       # just short of two jobs' length
    (70.0, 0.0, 3),
    (20.0, 0.0, 1),       # under one job: still one
])
def test_job_count_rounds_and_reads_past_stalls(seconds, stall, count):
    t0 = 100.0
    dispatch = [t0 + 0.375 * k for k in range(60)]
    dispatch[30:] = [t + stall for t in dispatch[30:]]
    job = harness.Job([], t0, dispatch[-1] + 0.375, [], dispatch, [], [])
    assert harness.job_count(seconds, job) == count


def test_trace_metrics_silent_without_a_trace():
    r = _readout([_job()])
    for name in ("device_idle_pct", "decode_mfu_pct",
                 "paged_attention_roofline"):
        assert harness.reader(name)(r) is None


def test_lane_steps_reconstruct_positions():
    steps = list(_job().lane_steps())
    # request 0: windows [0, 2) = 32 steps, useful while pos < 10 + 20 - 1
    r0 = [(g, p, u) for g, p, u in steps[:32]]
    assert [p for _, p, _ in r0] == list(range(32))
    assert sum(u for _, _, u in r0) == 29
    # request 2 admitted at window 1: steps 16..47 at positions 0..31
    r2 = steps[-32:]
    assert r2[0][:2] == (16, 0) and r2[-1][:2] == (47, 31)
    assert sum(u for _, _, u in r2) == 4 + 20 - 1
    # a stretch [1, 2) keeps only window 1's steps
    assert {g for g, _, _ in _job().lane_steps(1, 2)} == set(range(16, 32))


def test_flop_and_byte_counts_by_hand():
    s = CHATGLM
    per_layer = (4096 * 4096 + 2 * 4096 * 256 + 4096 * 4096
                 + 3 * 4096 * 13696)
    assert costs.matmul_params(s) == 28 * per_layer + 4096 * 65024
    assert costs.matmul_params(s) == 5_976_883_200
    ctx = 100
    assert costs.token_flops(s, ctx) == \
        2 * 5_976_883_200 + 28 * 4 * 32 * 128 * ctx
    # one block of K and V for both kv groups: 2 * 2 * 16 * 128 bf16
    assert costs.kv_block_bytes(s, 16) == 16384
    flops, nbytes = costs.attention_call(s, 33, 16)
    assert flops == 4 * 32 * 128 * 33
    assert nbytes == 3 * 16384 + 2 * 32 * 128 * 2
    assert costs.attention_layers(s, 33, 16) == [(flops, nbytes)] * 28


class T:                                         # a trace summary
    window_s, busy_s = 2.0, 1.5
    kernel_s = {"paged_attention": 0.01}


def test_roofline_and_mfu_by_hand():
    # the formulas the readers had before a family priced its own layers:
    # one GLM layer call times 28, and token_flops of every useful lane-step
    job = _job()
    r = _readout([job], T(), (0, 0, 4))
    pk = peaks.lookup("TPU v5 lite")
    calls = {}
    for g, pos, _ in job.lane_steps(0, 4):
        f, b = costs.attention_call(CHATGLM, pos + 1, 16)
        calls.setdefault(g, [0, 0])
        calls[g][0] += f
        calls[g][1] += b
    least = sum(max(b / pk.hbm_bytes_per_s, f / pk.bf16_flops)
                for f, b in calls.values()) * 28
    assert harness.reader("paged_attention_roofline")(r) == \
        pytest.approx(100 * least / 0.01, rel=1e-9)
    useful = sum(costs.token_flops(CHATGLM, p + 1)
                 for _, p, u in job.lane_steps(0, 4) if u)
    assert harness.reader("decode_mfu_pct")(r) == \
        pytest.approx(100 * useful / (2.0 * 197e12), rel=1e-9)
    assert harness.reader("device_idle_pct")(r) == pytest.approx(25.0)


@dataclasses.dataclass(frozen=True)
class StubShapes:
    layers: int
    window: int
    vocab: int


def _register_family(monkeypatch, family, with_costs=True):
    """A family of two layers, full attention then attention over a window
    of the last `window` positions, registered in memory under
    bench.models: no file of the benchmark names it or is touched."""
    program = types.ModuleType(f"bench.models.{family}")
    reference = types.ModuleType(f"bench.models.{family}_reference")
    reference.shapes = lambda cfg: StubShapes(
        cfg["num_layers"], cfg["window"], cfg["vocab_size"])
    modules = [program, reference]
    if with_costs:
        stub_costs = types.ModuleType(f"bench.models.{family}_costs")
        stub_costs.token_flops = lambda s, ctx: 1000 + 10 * ctx

        def attention_layers(s, ctx, block_tokens):
            seen = [ctx, min(ctx, s.window)]
            return [(4 * ctx, 100 * -(-seen[0] // block_tokens)),
                    (10**6 * seen[1], 100 * -(-seen[1] // block_tokens))]
        stub_costs.attention_layers = attention_layers
        modules.append(stub_costs)
    for m in modules:
        monkeypatch.setitem(sys.modules, m.__name__, m)
    return modules


def _stub_root(tmp_path, family):
    """A checkout whose BENCHMARK.json has one cell of a `family` model on
    the chat mix, with the real metric entries."""
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "stub-2l", "file": "stub.json"}]
    bench["workloads"] = [{"name": "stub-2l.chat", "config": "stub-2l",
                           "traffic": "chat", "chips": 1}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp_path / "stub.json").write_text(json.dumps(
        {"family": family, "num_layers": 2, "window": 8,
         "vocab_size": 256}))
    return tmp_path


def test_family_costs_found_by_name_price_each_layer(monkeypatch, tmp_path):
    program, reference, stub_costs = _register_family(monkeypatch, "stub")
    cell = harness.resolve("stub-2l.chat", _stub_root(tmp_path, "stub"))
    assert (cell.family, cell.reference, cell.costs) == \
        (program, reference, stub_costs)
    assert cell.shapes == StubShapes(2, 8, 256)
    # two lanes, each a 4-token prompt and 12 tokens, through window 0:
    # positions 0..15, useful while pos < 15
    job = harness.Job([traffic.Spec([1] * 4, 12)] * 2, 0.0, 1.0,
                      [C([5] * 12, (0, 1))] * 2, [0.0], [], [])
    r = harness.Readout(cell.shapes, cell.costs, peaks.lookup("TPU v5 lite"),
                        4, [job], T(), (0, 0, 1))
    # layer 0 reads ceil(ctx / 4) blocks of 100 bytes, 40 over ctx 1..16,
    # bound by bytes; the windowed layer's 10**6 * min(ctx, 8) FLOPs, 100
    # over ctx 1..16, bound by FLOPs; both lanes at every step
    least = 2 * 100 * 40 / 819e9 + 2 * 10**6 * 100 / 197e12
    assert harness.reader("paged_attention_roofline")(r) == \
        pytest.approx(100 * least / 0.01, rel=1e-12)
    # 15 useful steps a lane: 2 * (15 * 1000 + 10 * (1 + ... + 15))
    assert harness.reader("decode_mfu_pct")(r) == \
        pytest.approx(100 * 2 * 16_200 / (2.0 * 197e12), rel=1e-12)


def test_family_without_costs_is_refused(monkeypatch, tmp_path):
    _register_family(monkeypatch, "nocosts", with_costs=False)
    with pytest.raises(ModuleNotFoundError,
                       match=re.escape("bench.models.nocosts_costs")):
        harness.resolve("stub-2l.chat", _stub_root(tmp_path, "nocosts"))


def test_peak_table_refuses_unknown_kind():
    assert peaks.lookup("TPU v5 lite").hbm_bytes_per_s == 819e9
    with pytest.raises(KeyError, match="no peak table entry"):
        peaks.lookup("TPU v4")


def test_traffic_sizes_do_not_depend_on_the_seed():
    mix = traffic.load("chat")
    a = traffic.job(mix, 65024, 2**40 + 3)
    b = traffic.job(mix, 65024, 5)
    size = [(len(x.prompt), x.max_new) for x in a]
    assert size == [(len(x.prompt), x.max_new) for x in b]
    assert size == traffic.sizes(mix)
    assert [x.prompt for x in a] != [x.prompt for x in b]
    assert [x.prompt for x in a] == \
        [x.prompt for x in traffic.job(mix, 65024, 2**40 + 3)]
    p = np.array([len(x.prompt) for x in a])
    o = np.array([x.max_new for x in a])
    # LMSYS-Chat-1M's mean lengths, the response's lowered by its cut at 512
    assert len(a) == 32 and p.mean() == pytest.approx(69.5, rel=0.05)
    assert o.mean() == pytest.approx(197, abs=2)
    assert p.min() >= 4 and p.max() <= 512 and o.min() >= 4 and o.max() <= 512


def test_every_mix_fits_its_lanes_and_reference_rows():
    for path in sorted((BENCH / "traffic").glob("*.json")):
        mix = json.loads(path.read_text())
        longest = max(p + o for p, o in traffic.sizes(mix))
        assert longest < mix["max_len"], path.name
        assert longest - 1 <= check.row_length(mix), path.name
