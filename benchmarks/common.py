"""Shared benchmark harness utilities."""
from __future__ import annotations

import json
import os
import time
from typing import Callable, Dict, List

import numpy as np

from repro.data.crestkv import CrestKV, default_sim_config

# default scale: finishes in seconds per cell; --full matches the paper's
# 10M keys (metadata-only, still laptop-feasible but minutes per cell)
N_KEYS = 120_000
N_OPS = 4_000_000
WINDOW = 600_000
FULL_N_KEYS = 10_000_000
FULL_N_OPS = 100_000_000


def run_crest(structure: str, workload: str, *, backend: str = "proactive",
              enabled: bool = True, n_keys: int = N_KEYS,
              n_ops: int = N_OPS, window: int = WINDOW,
              hbm_target_bytes: int = 0, seed: int = 0,
              active_frac: float = 1 / 3):
    cfg = default_sim_config(n_keys, backend=backend, enabled=enabled,
                             hbm_target_bytes=hbm_target_bytes)
    kv = CrestKV(structure, n_keys, cfg, seed=seed)
    t0 = time.perf_counter()
    stats = kv.run(workload, n_ops, window_ops=window, seed=seed + 1,
                   active_frac=active_frac)
    wall = time.perf_counter() - t0
    return kv, stats, wall


def steady(windows: List[Dict], key: str, tail: int = 4) -> float:
    """Mean of a metric over the last `tail` windows (steady state)."""
    xs = [w[key] for w in windows[-tail:]]
    return float(np.mean(xs)) if xs else float("nan")


def timed(fn: Callable, *args, warmup: int = 1, iters: int = 5) -> float:
    """us per call (after warmup, best-effort block_until_ready)."""
    import jax
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def emit(name: str, us_per_call: float, derived: str) -> None:
    """One CSV row on stdout: name,us_per_call,derived."""
    print(f"{name},{us_per_call:.1f},{derived}")


def emit_json(name: str, record: Dict, out_dir: str = ".") -> str:
    """Write one benchmark record to `BENCH_<name>.json` (the repo's perf
    trajectory artifacts) and echo it to stdout; creates `out_dir` if
    missing (smoke runs point at the gitignored `bench_out/` scratch
    dir). Returns the path."""
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"BENCH_{name}.json")
    with open(path, "w") as fh:
        json.dump(record, fh, indent=2, sort_keys=True)
        fh.write("\n")
    print(f"{name}: {json.dumps(record, sort_keys=True)}")
    return path
