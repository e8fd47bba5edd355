#!/usr/bin/env python3
"""The readings a cell's output limit is set from, on the chip, at the
cell's own size: for each seed, one job of the cell's traffic through the
program, and the widest gap of its served tokens against the reference
(the program's reading); with `--control`, on the same prompts and
served tokens, the widest gap of the tokens that the reference computed
in float8 puts first (the control's reading, which has to fail).

    python3 bench/readings.py --workload <cell> --seeds 1 2 3 [--control]

One process for all seeds: the program is built and compiled once, the
weights are drawn anew for each seed. Prints one JSON line per seed.
Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from bench import check, harness, traffic
    from bench.run import device_check, enable_compile_cache
    cell = harness.resolve(args.workload)
    device_check(cell.chips)
    enable_compile_cache()
    ref, s = cell.reference, cell.shapes
    srv, params = harness.build(cell, args.seeds[0])
    disp = harness.Dispatches(srv)
    harness.run_job(srv, params, traffic.warmup(cell.mix, s.vocab), disp)
    for seed in args.seeds:
        if params is None:
            params = jax.block_until_ready(
                cell.family.program_params(s, ref.seed_key(seed)))
        job = harness.run_job(srv, params, traffic.job(cell.mix, s.vocab,
                                                       seed), disp)
        params = None
        pairs = check.sample([job], cell.mix["check_requests"], seed)
        tokens, targets = check.rows(pairs, [job], check.row_length(cell.mix))
        tokens, targets = jnp.asarray(tokens), jnp.asarray(targets)
        w = ref.weights(s, ref.seed_key(seed))
        t0 = time.perf_counter()
        prog = float(np.asarray(ref.gaps(s, w, tokens, targets)).max())
        t1 = time.perf_counter()
        out = {"seed": seed, "program": prog, "job_s": job.seconds,
               "reference_s": t1 - t0,
               "served": int((np.asarray(targets) >= 0).sum()),
               "bad": check.bad_completions(job.specs, job.done, s.vocab)}
        if args.control:
            out["control"] = float(np.asarray(
                ref.gaps(s, w, tokens, targets, "fp8")).max())
        del w
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
