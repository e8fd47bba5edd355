"""The DeepSeek-V2 family on the benchmark's side: its work counts at the
published widths against hand-worked numbers, the expert counter's
reader, and a run of its cell end to end on the CPU at a smoke size (one
dense and two MoE layers of width 64, latent 32 + rope 8, 2 of 8 experts
held), which is `correct`, and not with the float8 control in the
reference's place."""
import dataclasses
import time
from types import SimpleNamespace

import pytest

from bench import harness
from bench.compile_clock import CompileClock
from bench.models import deepseek_v2_costs as costs

CELL = "deepseek-v2-lite.chat-ep4"


def test_costs_at_published_widths_by_hand():
    s = harness.resolve(CELL).shapes
    assert (s.layers, s.dense_layers, s.experts, s.held, s.top_k) == \
        (27, 1, 64, 16, 6)
    # per layer, attention in the absorbed form: q 2048 x 3072, kv_a
    # 2048 x 576, W_UK and W_UV 16 x 128 x 512 each, o 2048 x 2048
    assert costs.attention_params(s) == 13_762_560
    # 27 x 13,762,560 + layer 0's MLP 3 x 2048 x 10944 + 26 x (router
    # 2048 x 64 + shared 3 x 2048 x 2816 + 1.5 experts of 3 x 2048 x 1408)
    # + the head 2048 x 102400
    assert costs.matmul_params(s) == 371_589_120 + 67_239_936 \
        + 26 * (131_072 + 17_301_504 + 12_976_128) + 209_715_200
    # 2 FLOPs a weight, and 27 x (2 x 16 x 576 + 2 x 16 x 512) a position
    assert costs.token_flops(s, 1) == 2_878_341_120 + 940_032
    assert costs.token_flops(s, 1024) == 2_878_341_120 + 1024 * 940_032
    # one latent block: 16 tokens x 576 x 2 B; q 16 x 576 and out 16 x 512
    flops, nbytes = costs.attention_call(s, 16, 16)
    assert nbytes - 16 * (576 + 512) * 2 == 18_432
    assert flops == 16 * 34_816
    assert costs.attention_call(s, 17, 16)[1] - nbytes == 18_432
    assert len(costs.attention_layers(s, 100, 16)) == 27


def test_picks_per_expert_read_reader():
    read = harness.reader("moe.picks_per_expert_read")

    def readout(*logs):
        return SimpleNamespace(jobs=[SimpleNamespace(serve_log=list(g))
                                     for g in logs])
    log = [{"expert_picks": 90, "experts_read": 30},
           {"expert_picks": 30, "experts_read": 30}]
    assert read(readout(log, [{"expert_picks": 0, "experts_read": 20}])) \
        == pytest.approx(120 / 80)
    assert read(readout([{"lane_steps": 16}])) is None   # no counters
    assert read(readout([])) is None
    assert read(readout([{"expert_picks": 0, "experts_read": 0}])) is None


def smoke_cell():
    cell = harness.resolve(CELL)
    cell.shapes = dataclasses.replace(
        cell.shapes, layers=3, dense_layers=1, hidden=64, heads=4,
        kv_lora=32, nope=16, rope=8, v_head=16, ffn=96, moe_ffn=32,
        experts=8, held_first=2, held=2, vocab=256)
    cell.mix = dict(cell.mix, lanes=4, requests_per_job=10, max_len=256,
                    check_requests=8,
                    prompt=dict(mean=9, sigma=0.5, min=2, max=40),
                    output=dict(mean=45, sigma=0.5, min=20, max=80))
    return cell


def run(cell, seed=2**33 + 17):
    return harness.execute(cell, seed, 0.5, False, CompileClock(),
                           time.time())


def test_smoke_run_is_correct():
    res = run(smoke_cell())
    assert res["correct"], res["checks"]
    assert res["failed"] == 0
    log = [e for j in res["jobs"] for e in j.serve_log]
    assert all(e["experts_read"] == 16 * 2 * 2 for e in log)
    assert sum(e["expert_picks"] for e in log) > 0


def test_float8_control_is_not_correct(monkeypatch):
    cell = smoke_cell()
    gaps = cell.reference.gaps
    monkeypatch.setattr(cell.reference, "gaps",
                        lambda *a: gaps(*a, "fp8"))
    res = run(cell)
    assert not res["correct"]
    assert res["checks"]["max_logit_gap"]["value"] > \
        res["checks"]["max_logit_gap"]["limit"]
