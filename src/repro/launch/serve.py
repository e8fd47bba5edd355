"""Serving launcher: `python -m repro.launch.serve --arch glm4-9b
--reduced --requests 8` — batched decode with the HADES-managed paged KV
cache (runtime/server.py), reporting KV RSS + collector activity.

`--mode generate` (default) teacher-forces one fixed batch through
`Server.generate`; `--mode serve` drives the continuous-batching queue
(`Server.serve`): more requests than lanes, lane churn at one dispatch
per window, per-window RSS-vs-live gauges, the p50/p95 of each request's
queue wait, time to first token and completion time (from the `serve`
call), and the median host milliseconds of each window phase
(docs/serving.md). `--temperature/--top-k`
switch on in-scan sampling (a PRNG key is derived from --seed).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import backend as be
from repro.launch import compile_cache
from repro.models.model import Model
from repro.runtime.server import Request, Server, ServerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--mode", default="generate",
                    choices=("generate", "serve"),
                    help="fixed-batch generate or continuous-batching "
                         "queue serving")
    ap.add_argument("--requests", type=int, default=4,
                    help="batch lanes (generate) / queued requests "
                         "(serve)")
    ap.add_argument("--lanes", type=int, default=0,
                    help="serve mode: batch lanes (0 -> min(requests, 4))")
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="> 0 samples in-scan (greedy otherwise)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="top-k filter for sampled decode (0 = full "
                         "vocab)")
    ap.add_argument("--backend", default="proactive", choices=be.names(),
                    help="tiering backend (backend registry)")
    ap.add_argument("--hbm-target-mb", type=int, default=0,
                    help="pressure target / promote high watermark for "
                         "the reactive/cap/mglru/promote backends")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    be_params = be.pressure_params(args.backend, args.hbm_target_mb << 20)
    if args.hbm_target_mb and not be_params:
        ap.error(f"--hbm-target-mb is not applicable to {args.backend!r}"
                 " (it declares no pressure field)")

    compile_cache.enable()
    cfg = get_config(args.arch, reduced=args.reduced)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    lanes = args.requests if args.mode == "generate" else \
        (args.lanes or min(args.requests, 4))
    srv = Server(model, ServerConfig(
        batch=lanes, max_len=args.max_len,
        block_tokens=max(args.max_len // 16, 4), backend=args.backend,
        backend_params=be_params, temperature=args.temperature,
        top_k=args.top_k))
    rng = np.random.default_rng(args.seed)
    sample_key = jax.random.PRNGKey(args.seed + 1)

    if args.mode == "generate":
        prompts = jnp.asarray(
            rng.integers(0, cfg.vocab_size,
                         (args.requests, args.prompt_len)), jnp.int32)
        greedy = args.temperature <= 0
        out = srv.generate(params, prompts, max_new=args.max_new,
                           greedy=greedy,
                           key=None if greedy else sample_key)
        print(f"generated {out.shape} tokens; "
              f"KV RSS {srv.kv_rss_bytes()/2**20:.2f} MiB")
    else:
        reqs = [Request(prompt=rng.integers(0, cfg.vocab_size,
                                            (args.prompt_len,)).tolist(),
                        max_new=args.max_new,
                        temperature=args.temperature, top_k=args.top_k)
                for _ in range(args.requests)]
        key = sample_key if args.temperature > 0 else None
        t0 = time.perf_counter()
        results = srv.serve(params, reqs, key=key)
        n_windows = len(srv.serve_log)
        print(f"served {len(results)} requests on {lanes} lanes in "
              f"{n_windows} windows ({srv.dispatches} dispatches); "
              f"{sum(len(r.tokens) for r in results)} tokens")
        peak = max((e["rss_bytes"] for e in srv.serve_log), default=0.0)
        print(f"KV RSS peak {peak/2**20:.2f} MiB -> final "
              f"{srv.kv_rss_bytes()/2**20:.2f} MiB "
              f"(reclaimed after finishes)")
        for what, stamp in (("queue wait", "t_admitted"),
                            ("first token", "t_first_token"),
                            ("completion", "t_finished")):
            s = [getattr(r, stamp) - t0 for r in results]
            print(f"{what}: p50 {np.percentile(s, 50):.3f} s, "
                  f"p95 {np.percentile(s, 95):.3f} s")
        phases = srv.serve_log[0]["host_ms"]
        print("host ms per window (median): " + ", ".join(
            f"{p} {np.median([e['host_ms'][p] for e in srv.serve_log]):.3f}"
            for p in phases))
    for r in srv.reports[-3:]:
        print("  collector:", {k: round(v, 4) for k, v in r.items()})


if __name__ == "__main__":
    main()
