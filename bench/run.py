#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of BENCHMARK.json's `workloads`. The run checks that
JAX's first device is a TPU listed in bench/peaks.py and that there are as
many chips as the cell asks for (otherwise it exits 1 and prints no
result), keeps JAX's compilation cache at JAX_COMPILATION_CACHE_DIR or
else at `.jax_cache/` in the checkout, builds the program from the seed,
warms it up, measures whole jobs for `--seconds`, checks the served
tokens against the reference, and prints one JSON line last on standard
output: the end-to-end metrics with `--trace 0`, the per-layer metrics
and the breakdown of a profiled stretch with `--trace 1`. The numbers
compared by the check, each beside its limit, are the last lines on
standard error and the last key of the JSON line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

START = time.time()
ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def process_start() -> float:
    """When this process was created (else when this file began to run)."""
    try:
        import psutil
        return psutil.Process().create_time()
    except ImportError:
        return START


def fail(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr)
    sys.exit(1)


def device_check(chips: int):
    """The chip's peak table entry; exits 1 off a known TPU."""
    import jax
    from bench import peaks
    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"needs a TPU, JAX found {devs[0].platform}")
    if len(devs) < chips:
        fail(f"the cell needs {chips} chips, JAX found {len(devs)}")
    try:
        return peaks.lookup(devs[0].device_kind)
    except KeyError as e:
        fail(str(e))


def enable_compile_cache() -> str:
    import jax
    where = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", where)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return where


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import check, harness
    from bench.compile_clock import CompileClock
    cell = harness.resolve(args.workload)
    peak = device_check(cell.chips)
    enable_compile_cache()
    import jax
    res = harness.execute(cell, args.seed, args.seconds, bool(args.trace),
                          CompileClock(), process_start(), peak=peak)
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": res["memory_peak_bytes"]}
    line = {"correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"], "metrics": res["metrics"],
            "device": device}
    s = res["summary"]
    if s is not None:
        device.update(busy_s=s.busy_s, window_s=s.window_s)
        line["breakdown"] = {"device_ops": [list(x) for x in s.top_ops],
                             "idle_gaps": [list(x) for x in s.idle_gaps]}
    line["checks"] = res["checks"]
    iv = [(x, k, n) for n, j in enumerate(res["jobs"])
          for k, x in enumerate(j.intervals())]
    longest = max(iv, default=(0.0, 0, 0))
    print(f"bench: {len(res['jobs'])} jobs, "
          f"{sum(j.seconds for j in res['jobs']):.3f} s; longest window "
          f"{longest[0]:.3f} s (job {longest[2]}, window {longest[1]})",
          file=sys.stderr)
    print(check.report(res["checks"]), file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
