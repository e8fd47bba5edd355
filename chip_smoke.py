#!/usr/bin/env python3
"""Chip smoke test: chatglm3-6b served on one TPU through the normal path.

    python chip_smoke.py [--seed N]

Builds the full chatglm3-6b model (28 layers at the published widths,
bf16, random weights from --seed) with `Model`, and serves greedy requests
through `Server.serve` — scanned decode windows over the paged KV pool,
the Pallas `paged_attention` kernel, and the fused collect + MIAD +
backend step. Each phase prints one line as it ends; any failure raises,
exits non-zero and prints no result. The last line of standard output is
`{"ok": true, "device": {...}}`, the device as JAX reports it.

Phases:
  device     JAX's first device must be a TPU; anything else exits 1.
  init       jitted parameter init: bytes, wall and compile seconds.
  lower      the serving window lowered for this chip: its HLO must hold
             the Pallas paged_attention custom call.
  serve      16 requests on 8 lanes: tokens, windows, dispatches (must
             equal windows), peak and final KV RSS (final must be 0),
             device peak bytes, compile and run seconds.
  kernel     on live KV state (a fixed batch left in the pool by
             `generate`), `kvcache.attend` for one layer with the Pallas
             kernel and with its jnp oracle on the same q: the largest
             output difference within its bf16 tolerance, and the same
             access bits recorded in the object table.
  collector  the same requests again with `ServerConfig(use_pallas=True)`
             (the access_scan and migrate kernels): same tokens, same
             per-window collector reports and gauges as the jnp collector.

The persistent compilation cache follows `JAX_COMPILATION_CACHE_DIR`,
or else `.jax_cache/` in the checkout (repro.launch.compile_cache).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.launch import compile_cache  # noqa: E402
from repro.models import kvcache as kvc  # noqa: E402
from repro.models.model import Model  # noqa: E402
from repro.runtime.server import Request, Server, ServerConfig  # noqa: E402

ARCH = "chatglm3-6b"
SERVER = dict(batch=8, max_len=1024, block_tokens=16, window=16)
N_REQUESTS, PROMPT_LEN, MAX_NEW = 16, (32, 128), 32
# the fixed batch left live in the pool for the kernel check: 112 + 17 - 1
# = 128 steps, whole windows only (one program shape)
LIVE_PROMPT, LIVE_NEW = 112, 17
# the kernel/oracle comparison: bf16 K/V/q, and on TPU both paths' f32
# matmuls run at default precision (operands rounded to bf16, 2^-9
# relative); the oracle also rounds its scores to bf16 before the softmax
# and both round the output to bf16. With O(1) scores these add to a few
# bf16 steps (2^-8 relative each) of the output's scale: allow 8.
OUT_TOL = 2.0 ** -5


class CompileClock:
    """Records the spans in which JAX traces, lowers and compiles (a
    cache load included), so a phase's compile time is reported apart
    from its run time. Traces nest (a jitted function traced inside
    another), so a phase's compile time is the union of the spans."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.spans = []
        jax.monitoring.register_event_time_span_listener(self._on)

    def _on(self, event, start, end, **_):
        if event in self.EVENTS:
            self.spans.append((start, end))

    def seconds(self, t0: float, t1: float) -> float:
        """Length of the union of the recorded spans within [t0, t1]."""
        total, reach = 0.0, t0
        for start, end in sorted(self.spans):
            start, end = max(start, reach), min(end, t1)
            if end > start:
                total += end - start
                reach = end
        return total


def phase(name: str, **fields) -> None:
    print(f"{name:<10} " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def timed(clock: CompileClock, fn):
    """(fn's result, wall seconds, compile seconds within them); JAX
    stamps its spans with time.time()."""
    t0 = time.time()
    out = jax.block_until_ready(fn())
    t1 = time.time()
    return out, t1 - t0, clock.seconds(t0, t1)


def device_peak_bytes() -> int:
    return jax.devices()[0].memory_stats()["peak_bytes_in_use"]


def make_requests(cfg, seed: int):
    rng = np.random.default_rng(seed)
    lens = rng.integers(PROMPT_LEN[0], PROMPT_LEN[1] + 1, N_REQUESTS)
    return [Request(prompt=rng.integers(0, cfg.vocab_size, n).tolist(),
                    max_new=MAX_NEW) for n in lens]


def serve_phase(name, clock, srv, params, reqs, vocab):
    done, wall, comp = timed(clock, lambda: srv.serve(params, reqs))
    windows = len(srv.serve_log)
    tokens = sum(len(c.tokens) for c in done)
    peak_rss = max(e["rss_bytes"] for e in srv.serve_log)
    final_rss = srv.kv_rss_bytes()
    peak_dev = device_peak_bytes()
    phase(name, requests=len(done), tokens=tokens, windows=windows,
          dispatches=srv.dispatches, kv_rss_peak_bytes=int(peak_rss),
          kv_rss_final_bytes=int(final_rss), device_peak_bytes=peak_dev,
          compile_s=f"{comp:.3f}", run_s=f"{wall - comp:.3f}")
    if srv.dispatches != windows:
        raise RuntimeError(f"{srv.dispatches} dispatches for {windows} "
                           "windows: serving must take one per window")
    if final_rss != 0:
        raise RuntimeError(f"KV RSS {final_rss} after the drain, not 0")
    for c, r in zip(done, reqs):
        if not 1 <= len(c.tokens) <= r.max_new or \
                not all(0 <= t < vocab for t in c.tokens):
            raise RuntimeError(f"request {c.rid}: bad completion {c}")
    return done, list(srv.reports), list(srv.serve_log)


def kernel_phase(clock, srv, params, cfg, seed):
    """Paged attention's Pallas kernel against its jnp oracle on the
    server's live KV pool."""
    rng = np.random.default_rng(seed + 1)
    prompts = jnp.asarray(rng.integers(0, cfg.vocab_size,
                                       (SERVER["batch"], LIVE_PROMPT)),
                          jnp.int32)
    srv.reset()
    srv.generate(params, prompts, max_new=LIVE_NEW)
    layer = cfg.num_layers - 1
    q = jax.random.normal(jax.random.PRNGKey(seed + 2),
                          (SERVER["batch"], cfg.num_heads,
                           cfg.resolved_head_dim), jnp.bfloat16)
    attend = jax.jit(kvc.attend, static_argnums=(0, 2),
                     static_argnames="use_pallas")
    (out_p, st_p), _, comp = timed(
        clock, lambda: attend(srv.kv_cfg, srv.state, layer, q,
                              use_pallas=True))
    out_j, st_j = attend(srv.kv_cfg, srv.state, layer, q, use_pallas=False)
    out_p = np.asarray(out_p, np.float32)
    out_j = np.asarray(out_j, np.float32)
    diff = float(np.abs(out_p - out_j).max())
    scale = float(np.abs(out_j).max())
    pool_p = jax.tree.map(np.asarray, st_p["pool"])
    pool_j = jax.tree.map(np.asarray, st_j["pool"])
    same_bits = jax.tree.all(jax.tree.map(np.array_equal, pool_p, pool_j))
    touched = int(pool_p["win_accesses"] - np.asarray(
        srv.state["pool"]["win_accesses"]))
    phase("kernel", layer=layer, live_blocks=int(
              np.sum(np.asarray(srv.state["block_tables"][layer]) >= 0)),
          blocks_touched=touched, max_abs_diff=diff, oracle_max_abs=scale,
          tol=OUT_TOL * scale, same_access_bits=same_bits,
          compile_s=f"{comp:.3f}")
    if not np.isfinite(out_p).all() or diff > OUT_TOL * scale:
        raise RuntimeError(f"paged_attention differs from its oracle by "
                           f"{diff} (tolerance {OUT_TOL * scale})")
    if not same_bits or touched <= 0:
        raise RuntimeError("kernel and oracle recorded different access "
                           f"bits (touched {touched})")


def run(cfg, seed: int, clock: CompileClock) -> None:
    """Every phase after the device check, on `cfg` at full size."""
    model = Model(cfg)
    params, wall, comp = timed(
        clock, lambda: model.init(jax.random.PRNGKey(seed)))
    n_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    phase("init", arch=cfg.name, layers=cfg.num_layers, d_model=cfg.d_model,
          param_bytes=n_bytes, wall_s=f"{wall:.3f}", compile_s=f"{comp:.3f}")

    srv = Server(model, ServerConfig(**SERVER))
    hlo = srv.lower_serve_window(params).as_text()
    kernels = [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln]
    has_pa = any("paged_attention" in k for k in kernels)
    phase("lower", tpu_custom_calls=len(kernels), paged_attention=has_pa)
    if not has_pa:
        raise RuntimeError("the serving window does not call the Pallas "
                           "paged_attention kernel")

    reqs = make_requests(cfg, seed)
    done, reports, log = serve_phase("serve", clock, srv, params, reqs,
                                     cfg.vocab_size)
    kernel_phase(clock, srv, params, cfg, seed)
    del srv

    srv_p = Server(model, ServerConfig(**SERVER, use_pallas=True))
    done_p, reports_p, log_p = serve_phase("serve_pk", clock, srv_p, params,
                                           reqs, cfg.vocab_size)
    same_tokens = [c.tokens for c in done] == [c.tokens for c in done_p]
    same_reports = len(reports) == len(reports_p) and all(
        a.keys() == b.keys() and
        np.array_equal([a[k] for k in a], [b[k] for k in a], equal_nan=True)
        for a, b in zip(reports, reports_p))
    phase("collector", same_tokens=same_tokens, same_reports=same_reports,
          reports=len(reports), same_gauges=log == log_p)
    if not (same_tokens and same_reports and log == log_p):
        raise RuntimeError("the Pallas collector diverged from the jnp "
                           "collector")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cache = compile_cache.enable()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        sys.exit(1)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    phase("device", **device, compile_cache=cache)
    run(get_config(ARCH), args.seed, CompileClock())
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
