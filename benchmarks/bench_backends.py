"""Backend sweep on the production engine (Figure-7-style): every
registered tiering backend x workload -> RSS saved vs overhead, with
host-side dispatch accounting proving the stateful backends (mglru,
promote) run INSIDE the fused serving window (1 dispatch per window,
same as the stateless ones). Emits `BENCH_backends.json` via
benchmarks.common.emit_json — a perf-trajectory artifact.

    PYTHONPATH=src:. python benchmarks/bench_backends.py [--smoke]

Workloads (each window = `every` batched ops, K object ids per op):

  zipf    a scattered hot eighth is hammered with reads; the rest cools
          — the paper's skewed-serving case where tidying + any
          demoting backend should cut RSS at ~zero fault cost.
  phase   three phases: hot set A (densified into HOT superblocks), a
          long detour to set B (A cools and gets demoted IN PLACE in
          the HOT region), then STORES to A. Stores neither fault nor
          migrate (A's heap is already HOT), so only a page-level
          promoter re-tiers A — the case the promote backend exists
          for; every other backend leaves the written-hot set in slow
          memory.
  scan    a rotating sequential sweep touches everything eventually —
          the anti-LRU adversary where hotness-blind eviction (cap)
          thrashes.

Reported per cell: steady RSS fraction of the footprint, wall time per
window, faults, backend demote/promote totals, dispatches per window
(asserted == 1: the fused-window contract is backend-independent).
"""
from __future__ import annotations

import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit_json
from repro.core import HadesOptions, make_config
from repro.core import backend as be
from repro.core import engine as eng
from repro.core import object_table as ot
from repro.core.collector import CollectorConfig

EVERY, K = 16, 64


def end_load_phase(state):
    """Clear load-time access bits + window counters (allocation stores
    are not workload accesses) — what `Hades.end_load_phase` / CrestKV's
    load do, so the run starts with a fresh observation window."""
    return dict(state,
                table=ot.clear_access_and_atc(state["table"]),
                slot_ref=jnp.zeros_like(state["slot_ref"]),
                win_accesses=jnp.zeros((), jnp.int32),
                win_promos=jnp.zeros((), jnp.int32),
                win_faults=jnp.zeros((), jnp.int32))


def build_trace(cfg, workload: str, n_windows: int, rng):
    n = cfg.max_objects
    steps = []
    perm = rng.permutation(n)
    n_a = max(n // 8, K)
    set_a = perm[:n_a]
    set_b = perm[n_a:n_a + n // 4]    # disjoint from A: the detour must
    # not fault A's superblocks back in
    wvals = rng.normal(size=(K, cfg.slot_words)).astype(np.float32)
    for t in range(n_windows * EVERY):
        w = t // EVERY
        if workload == "zipf":
            steps.append(("read", set_a[rng.integers(0, len(set_a), K)],
                          None))
        elif workload == "phase":
            # detour is SHORT (3 windows): long enough for pressure to
            # demote A's now-idle superblocks, short enough that A stays
            # in the HOT heap (ciw <= C_t) — so the write phase hits
            # HOT-heap objects on HOST superblocks, the page-level
            # promotion case no frontend migration can cover
            build = max(n_windows // 4, 1)
            if w < build:                           # build: A densifies
                steps.append(("read",
                              set_a[rng.integers(0, len(set_a), K)], None))
            elif w < build + 3:                     # detour: A demoted
                steps.append(("read",
                              set_b[rng.integers(0, len(set_b), K)], None))
            else:                                   # stores to cold-hot A
                steps.append(("write",
                              set_a[rng.integers(0, len(set_a), K)],
                              wvals))
        else:  # scan: rotating sequential sweep
            lo = (t * K) % n
            ids = (np.arange(lo, lo + K)) % n
            steps.append(("read", ids, None))
    return eng.make_trace(cfg, steps, k=K)


def run_windows(engine, state, trace):
    """Window-by-window streaming (the serving shape): one dispatch per
    window, reports pulled between dispatches. The engine DONATES its
    state input (in-place pool updates), so each run works on a private
    copy and the caller's `state` stays alive for the next repeat."""
    state = jax.tree.map(lambda x: x.copy(), state)
    t = int(trace["op"].shape[0])
    dispatches = 0
    reports = []
    for lo in range(0, t, EVERY):
        chunk = {k2: v[lo:lo + EVERY] for k2, v in trace.items()}
        state, _, rep = engine.run_window(state, chunk, lo)
        reports.extend(eng.window_reports(rep))
        dispatches += 1
    jax.block_until_ready(state["table"])
    return state, reports, dispatches


def _best_of(fn, repeats: int) -> float:
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def main(smoke: bool = False):
    n_objects = 1024
    n_windows = 8 if smoke else 16
    repeats = 1 if smoke else 3
    cfg = make_config(max_objects=n_objects, slot_words=32, sb_slots=64,
                      page_slots=8, slack=1.5)
    rng = np.random.default_rng(0)

    # pressure target: half the allocated footprint (sb-aligned)
    footprint_sbs = -(-n_objects // cfg.sb_slots)
    target = (footprint_sbs // 2) * cfg.sb_bytes
    systems = {
        "null": be.make("null"),
        "reactive": be.make("reactive", hbm_target_bytes=target),
        "proactive": be.make("proactive"),
        "cap": be.make("cap", hbm_target_bytes=target),
        "mglru": be.make("mglru", hbm_target_bytes=target),
        "promote": be.make("promote", hbm_high_bytes=target,
                           hbm_low_bytes=target // 2),
    }
    assert set(systems) == set(be.names()), "sweep must cover the registry"

    record = {"n_objects": n_objects, "collect_every": EVERY,
              "ops_per_step": K, "n_windows": n_windows,
              "hbm_target_bytes": target, "smoke": smoke}
    vals = rng.normal(size=(n_objects, cfg.slot_words)).astype(np.float32)
    footprint = float(footprint_sbs * cfg.sb_bytes)

    for workload in ("zipf", "phase", "scan"):
        # per-workload deterministic stream: cells are reproducible in
        # isolation and don't shift when the sweep order changes
        trace = build_trace(cfg, workload, n_windows,
                            np.random.default_rng(0))
        for name, backend in systems.items():
            opts = HadesOptions(collect_every=EVERY, backend=backend,
                                collector=CollectorConfig())
            engine = eng.Engine(cfg, opts)
            base, _, _ = engine.step(engine.init(), "alloc",
                                     np.arange(n_objects), vals)
            base = end_load_phase(base)
            jax.block_until_ready(base["table"])

            state, reports, dispatches = run_windows(engine, base, trace)
            secs = _best_of(lambda: run_windows(engine, base, trace),
                            repeats)
            # host-side compiled-program launches (same accounting as
            # Server.dispatches): one run_window call per
            # window, every backend — the stateful ones compile into the
            # SAME single window program (their bstate is scan-carried;
            # a backend that needed a host round-trip would fail at
            # trace time, not add launches)
            dpw = dispatches / n_windows
            assert dpw == 1.0, \
                f"{name}: backend broke the fused window ({dpw} disp/win)"
            tail = reports[-max(n_windows // 4, 1):]
            cell = {
                "rss_frac": float(np.mean([r["rss_bytes"] for r in tail]))
                / footprint,
                "us_per_window": secs / n_windows * 1e6,
                "dispatches_per_window": dpw,
                "faults": int(state["total_faults"]),
                "demoted": int(sum(r["be_demoted"] for r in reports)),
                "promoted": int(sum(r["be_promoted"] for r in reports)),
            }
            record[f"{workload}_{name}"] = cell
            print(f"{workload:6s} {name:9s} rss={cell['rss_frac']:.2f} "
                  f"faults={cell['faults']:4d} "
                  f"dem={cell['demoted']:4d} prom={cell['promoted']:3d} "
                  f"{cell['us_per_window']:8.0f} us/win")

    out_dir = "bench_out" if smoke else "."
    os.makedirs(out_dir, exist_ok=True)
    emit_json("backends", record, out_dir=out_dir)
    return record


if __name__ == "__main__":
    main(smoke="--smoke" in sys.argv)
