"""A run's plumbing end to end on the CPU at the chatglm3-6b-smoke sizes
(2 layers of width 64, 4 heads over 2 kv groups of 16, FFN 128, 256 ids):
set-up, identical jobs through `Server.serve`, the reference check and the
metrics. Off a TPU the command itself refuses to run. With the timed path
broken underneath (a token altered where the program produces it), or
with the float8 control in the reference's place, `correct` comes out
false under the cell's own limit."""
import dataclasses
import os
import subprocess
import sys
import time
from pathlib import Path

import jax.numpy as jnp
import pytest

from bench import check, harness
from bench.compile_clock import CompileClock

ROOT = Path(__file__).resolve().parents[2]
CELL = "chatglm3-6b.chat"


def smoke_cell():
    cell = harness.resolve(CELL)
    cell.shapes = dataclasses.replace(
        cell.shapes, layers=2, hidden=64, heads=4, kv_heads=2, head_dim=16,
        ffn=128, vocab=256)
    cell.mix = dict(cell.mix, lanes=4, requests_per_job=10, max_len=256,
                    check_requests=8,
                    prompt=dict(mean=9, sigma=0.5, min=2, max=40),
                    output=dict(mean=45, sigma=0.5, min=20, max=80))
    return cell


def run(cell, seed=2**33 + 17, trace=False):
    return harness.execute(cell, seed, 0.5, trace, CompileClock(),
                           time.time())


def test_command_refuses_off_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload", CELL,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr
    assert not any(line.startswith("{") for line in out.stdout.splitlines())


def test_smoke_run_is_correct_and_counts():
    res = run(smoke_cell())
    assert res["correct"], res["checks"]
    assert res["failed"] == 0
    jobs = res["jobs"]
    assert res["attempted"] == 10 * len(jobs) >= 10
    # one dispatch per window, and every job replays the same requests
    assert all(len(j.dispatch_t) == len(j.serve_log) for j in jobs)
    assert len({tuple(tuple(c.tokens) for c in j.done) for j in jobs}) == 1
    e2e = harness.end_to_end(jobs, 1.0)
    assert e2e["out_tok_per_s"] > 0 and e2e["req_latency_p95_s"] > 0
    assert set(res["checks"]) == {"bad_completions", "max_logit_gap"}
    assert res["checks"]["max_logit_gap"]["limit"] == \
        check.limits(CELL)["max_logit_gap"]["limit"]


def test_traced_run_needs_device_ops():
    # the CPU's profile has no TPU plane: the reduction refuses rather
    # than report a device number from the host
    with pytest.raises(RuntimeError, match="no device op"):
        run(smoke_cell(), trace=True)


def test_compile_inside_the_window_is_an_error(monkeypatch):
    cell = smoke_cell()
    orig = harness.run_job
    calls = []

    def run_job(srv, params, specs, disp):
        calls.append(1)
        if len(calls) == 2:              # the first measured job
            jnp.zeros((3, 7, 11)).block_until_ready()  # a fresh shape
        return orig(srv, params, specs, disp)
    monkeypatch.setattr(harness, "run_job", run_job)
    with pytest.raises(RuntimeError, match="compilations inside"):
        run(cell)


def test_altered_token_is_not_correct(monkeypatch):
    from repro.runtime.server import Server
    step = Server._model_step

    def altered(self, params, state, tok):
        state, logits = step(self, params, state, tok)
        return state, jnp.roll(logits, 1, axis=-1)   # argmax moves by one
    monkeypatch.setattr(Server, "_model_step", altered)
    res = run(smoke_cell())
    assert not res["correct"]
    assert res["checks"]["max_logit_gap"]["value"] > \
        res["checks"]["max_logit_gap"]["limit"]


def test_float8_control_is_not_correct(monkeypatch):
    cell = smoke_cell()
    gaps = cell.reference.gaps
    monkeypatch.setattr(cell.reference, "gaps",
                        lambda *a: gaps(*a, "fp8"))
    res = run(cell)
    assert not res["correct"]
