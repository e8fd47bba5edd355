"""Continuous-batching serving benchmark: lane churn through the fused
decode windows at exactly ONE dispatch per window.

A zipf'd request mix (short prompts dominate, a heavy tail of long
generations) drives `Server.serve`: lanes admit from the queue, decode,
finish on EOS/max-tokens and FREE their KV through the pool op stream at
the next window boundary — the realistic generator of the paper's
hotness fragmentation (finished requests strand cold blocks interleaved
with live lanes' hot blocks across superblocks). Emits the
continuous-batching row as `BENCH_serve.json`:

  * tokens/sec over the whole churn run,
  * per-window KV-RSS vs live-bytes curves (RSS must TRACK live bytes
    via post-finish reclamation, not ride at peak allocation),
  * reclaimed-after-finish accounting.

In-script asserts (CI runs --smoke): exactly 1 dispatch per window, and
nonzero post-finish reclamation (final RSS < peak RSS).

    PYTHONPATH=src:. python benchmarks/bench_continuous.py [--smoke]
"""
from __future__ import annotations

import sys
import time

import jax
import numpy as np

from benchmarks.common import emit_json
from repro.models.model import build
from repro.runtime.server import Request, Server, ServerConfig


def _requests(n: int, rng: np.random.Generator, vocab: int,
              max_len: int) -> list:
    """Zipf'd request mix: prompt and output lengths are heavy-tailed,
    so lanes finish at very different times (the arrival churn a
    continuous batcher exists for)."""
    reqs = []
    for _ in range(n):
        p_len = int(np.clip(rng.zipf(1.8), 2, 10))
        max_new = int(np.clip(4 * rng.zipf(1.6), 4, max_len - p_len - 1))
        prompt = rng.integers(0, vocab, (p_len,)).tolist()
        reqs.append(Request(prompt=prompt, max_new=max_new))
    return reqs


def main(smoke: bool = False):
    w = 8
    batch = 4
    max_len = 64
    n_req = 10 if smoke else 32
    m = build("chatglm3-6b", reduced=True)
    params = m.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    srv = Server(m, ServerConfig(batch=batch, max_len=max_len,
                                 block_tokens=w, collect_every=w,
                                 window=w))
    reqs = _requests(n_req, rng, m.cfg.vocab_size, max_len)

    srv.serve(params, _requests(2, rng, m.cfg.vocab_size, max_len))
    t0 = time.perf_counter()
    results = srv.serve(params, reqs)       # warm: programs compiled
    wall = time.perf_counter() - t0

    n_windows = len(srv.serve_log)
    # the fused-window contract under lane churn: every window — lane
    # frees, admits, prompt forcing, sampling, collect+backend — was
    # exactly one host dispatch
    assert srv.dispatches == n_windows, \
        f"{srv.dispatches} dispatches for {n_windows} windows"
    assert all(r is not None and r.tokens for r in results)

    rss = [e["rss_bytes"] for e in srv.serve_log]
    live = [e["live_bytes"] for e in srv.serve_log]
    peak, final = max(rss), rss[-1]
    # finished lanes' KV left the pool through the op stream and the
    # collector/backend reclaimed the emptied superblocks: RSS tracks
    # live bytes down, it does not ride at peak allocation
    assert peak > 0 and final < peak, \
        f"no post-finish reclamation: peak={peak} final={final}"
    assert live[-1] == 0.0, "drain window left live KV objects behind"

    toks_total = sum(len(r.tokens) for r in results)
    record = {"continuous": {
        "arch": "chatglm3-6b-reduced", "smoke": smoke, "batch": batch,
        "window": w, "max_len": max_len, "n_requests": n_req,
        "n_windows": n_windows,
        "dispatches_per_window": srv.dispatches / n_windows,
        "tokens_per_sec": toks_total / wall,
        "generated_tokens": toks_total,
        "finished_eos": sum(r.finish_reason == "eos" for r in results),
        "rss_peak_bytes": peak, "rss_final_bytes": final,
        "reclaimed_after_finish_bytes": peak - final,
        "rss_curve": rss, "live_curve": live,
    }}
    out_dir = "bench_out" if smoke else "."
    emit_json("serve", record, out_dir=out_dir)
    return record


if __name__ == "__main__":
    main(smoke="--smoke" in sys.argv)
