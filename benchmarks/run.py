"""Benchmark aggregator: one run per paper table/figure + framework
benches. Prints ``name,us_per_call,derived`` CSV rows.

    PYTHONPATH=src python -m benchmarks.run [--full]

Default scale finishes on a laptop CPU in ~10 minutes; --full uses
paper-scale key counts (minutes per cell, metadata-only memory).
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

from benchmarks import (bench_embedding, bench_kernels,
                        fig2_page_utilization,
                        fig3_unreclaimable, fig6_crestdb, fig7_backends,
                        roofline_report, table1_structures)

# the serving bench lives outside this CSV aggregator:
# bench_continuous.py emits the BENCH_serve.json perf-trajectory artifact
SUITES = [
    ("fig2_page_utilization", fig2_page_utilization.main),
    ("fig3_unreclaimable", fig3_unreclaimable.main),
    ("fig6_crestdb", fig6_crestdb.main),
    ("fig7_backends", fig7_backends.main),
    ("table1_structures", table1_structures.main),
    ("bench_kernels", bench_kernels.main),
    ("bench_embedding", bench_embedding.main),
    ("roofline_report", roofline_report.main),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale runs (slow)")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    smoke = not args.full
    print("name,us_per_call,derived")
    failures = 0
    for name, fn in SUITES:
        if args.only and args.only not in name:
            continue
        t0 = time.time()
        try:
            fn(smoke=smoke)
            print(f"# {name} done in {time.time()-t0:.0f}s",
                  file=sys.stderr)
        except Exception:  # noqa: BLE001
            failures += 1
            print(f"# {name} FAILED", file=sys.stderr)
            traceback.print_exc()
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
