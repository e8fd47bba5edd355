"""Fused window-execution engine — one device dispatch per serving window.

The paper's 3%-overhead claim is about *tracking* cost, not dispatch cost;
a frontend that pays a host round-trip per op (separately jitted
read/write/alloc with a Python tick in between) measures the wrong thing.
This engine executes an entire serving window — `collect_every` batched
ops, the Object Collector pass, MIAD, MADV_COLD candidate marking, and the
backend step — as ONE `jax.jit`-compiled `lax.scan`:

    trace:  {"op": [T], "ids": [T, K], "values": [T, K, W]}
      |                       (K ops per step, ids < 0 are padding)
      v
    lax.scan over T steps:
        lax.switch(op)  -> pool.read / write / alloc / free
        step clock +1
        lax.cond(step % every == every-1 & overlap) -> arm ATC window
        lax.cond(step % every == 0) -> collect + backend  (fused)
      |
      v
    (state', read outputs [T, K, W], per-step reports)

Nothing inside a window may sync to the host; the per-step report pytree
has a fixed shape (zeros on non-collect steps, `did_collect` marks the
real ones) so callers pull results *after* the window. The `Hades`
frontend wrapper (core/frontend.py) rides the same machinery one step at
a time via `apply_step`, so the step-by-step and fused paths are
bit-identical (tests/test_engine.py asserts it).

Every op in a trace advances the window clock — including `free` (the
clock counts ops, not accesses; a data-dependent clock would not scan).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core import backend as be
from repro.core import collector as col
from repro.core import pool as pl

# op codes for batched traces (defined by the pool's unified op)
READ, WRITE = pl.OP_READ, pl.OP_WRITE
ALLOC, FREE = pl.OP_ALLOC, pl.OP_FREE
OP_CODES = {"read": READ, "write": WRITE, "alloc": ALLOC, "free": FREE}


@dataclasses.dataclass(frozen=True)
class EngineOptions:
    """Static window/collector/backend configuration (hashable; closed
    over by the jitted window program). Field-compatible with the old
    `HadesOptions` — frontend.py aliases it."""
    collect_every: int = 8
    # a backend.Backend (from backend.make), a deprecated BackendConfig,
    # or a registered name — normalized via backend.as_backend
    backend: Union[be.Backend, be.BackendConfig, str] = dataclasses.field(
        default_factory=lambda: be.make("reactive"))
    collector: col.CollectorConfig = dataclasses.field(
        default_factory=col.CollectorConfig)
    enabled: bool = True           # False = allocator-only (no tidying)
    # Arm ATC tracking for the window preceding each collect. The paper's
    # scope guards decrement on function EXIT; in a synchronous loop every
    # step has exited before the collector runs, so nothing is in flight
    # and arming would only veto migrations spuriously. Set True when the
    # runtime overlaps step dispatch with collection (async serving) —
    # then ATC>0 marks objects a concurrent step may still dereference.
    overlap_collect: bool = False


def zero_report() -> Dict[str, jax.Array]:
    """The no-collect report: same pytree structure/dtypes as a real one
    so `lax.cond` branches agree."""
    i32 = functools.partial(jnp.zeros, (), jnp.int32)
    f32 = functools.partial(jnp.zeros, (), jnp.float32)
    report = {
        "moved_to_hot": i32(), "moved_to_cold": i32(),
        "skipped_atc": i32(),
        "promotion_rate": f32(),
        "proactive_ok": jnp.zeros((), jnp.bool_),
        "ciw_threshold": f32(),
        "win_accesses": i32(), "win_faults": i32(),
        "rss_bytes": f32(), "host_bytes": f32(),
        "did_collect": jnp.zeros((), jnp.bool_),
    }
    report.update(be.zero_telemetry())
    return report


def collect_and_backend(pool_cfg: pl.PoolConfig, col_cfg: col.CollectorConfig,
                        backend: be.Backend, state: Dict
                        ) -> Tuple[Dict, Dict[str, jax.Array]]:
    """Collector pass + backend step as one fused transition. The backend
    sees the closing window's superblock stats (pre-clear), exactly as the
    old two-dispatch Hades.collect did, plus its own carried state
    (`state["bstate"]`, threaded through the scan carry so stateful
    backends run inside the single-dispatch window); RSS/host byte gauges
    are computed on-device so callers never sync mid-window."""
    state, report = col.collect(pool_cfg, col_cfg, state)
    stats = report.pop("sb_stats")
    signals = {"proactive_ok": report["proactive_ok"],
               "epoch": state["epoch"]}
    with jax.named_scope("backend"):
        bstate, tier, evict, telemetry = backend.step(
            pool_cfg, state["bstate"], stats, state["sb_tier"],
            state["sb_evict"], signals)
    state = dict(state, bstate=bstate, sb_tier=tier, sb_evict=evict)
    report.update(telemetry)
    occupied = stats["occupancy"] > 0
    sb_bytes = float(pool_cfg.sb_bytes)
    report["rss_bytes"] = jnp.sum(
        occupied & (tier == pl.HBM)).astype(jnp.float32) * sb_bytes
    report["host_bytes"] = jnp.sum(
        occupied & (tier == pl.HOST)).astype(jnp.float32) * sb_bytes
    report["did_collect"] = jnp.ones((), jnp.bool_)
    return state, report


# ---------------------------------------------------------------------------
# single step — the Hades wrapper's path (op/collect decisions are static:
# the host knows the deterministic window clock, so no device cond needed)
# ---------------------------------------------------------------------------
def apply_step(pool_cfg: pl.PoolConfig, col_cfg: col.CollectorConfig,
               backend: be.Backend, state: Dict, ids: jax.Array,
               values: Optional[jax.Array], *, op: str,
               do_arm: bool = False, do_collect: bool = False
               ) -> Tuple[Dict, Optional[jax.Array], Dict[str, jax.Array]]:
    """One op + its share of the window protocol, fused into a single
    compiled program: apply `op`, then (statically) arm and/or run
    collect+backend. Returns (state, read_values_or_None, report)."""
    out = None
    if op == "read":
        out, state = pl.read(pool_cfg, state, ids)
    elif op == "write":
        state = pl.write(pool_cfg, state, ids, values)
    elif op == "alloc":
        state = pl.alloc(pool_cfg, state, ids, values)
    elif op == "free":
        state = pl.free(pool_cfg, state, ids)
    else:
        raise ValueError(op)
    if do_arm:
        state = col.arm(state)
    if do_collect:
        state, report = collect_and_backend(pool_cfg, col_cfg, backend,
                                            state)
    else:
        report = zero_report()
    return state, out, report


# ---------------------------------------------------------------------------
# the window protocol over an ARBITRARY per-step transition
# ---------------------------------------------------------------------------
def window_program(step_fn, collect_fn, arm_fn, *, every: int,
                   enabled: bool = True, overlap: bool = False,
                   zero_report_fn=zero_report, pre_fn=None):
    """Build the two fused-window program shapes over an arbitrary
    per-step transition — the machinery behind `make_run_window`, reused
    by the server's scanned decode windows (runtime/server.py):

        step_fn(state, xs)  -> (state, out_pytree)     one window step
        collect_fn(state)   -> (state, report)         fused collect+backend
        arm_fn(state)       -> state                   ATC arming (epoch)
        pre_fn(state, exs)  -> state                   window-ENTRY events

    Returns (run_generic(state, xs, step0), run_aligned(state, xs)), both
    UNJITTED so callers can close extra operands (e.g. model params) over
    `step_fn` and jit at their own boundary. Window semantics are the
    engine contract: the clock ticks once per step; arm fires after the
    step at clock % every == every-1 (overlap only); collect+backend runs
    after the step at clock % every == 0. `run_aligned` requires
    T % every == 0 and step0 % every == 0 and is cond-free (one collect
    per window, statically placed); `run_generic` handles any T/step0
    with a cond-gated collect. Reports come back per-STEP in both shapes
    (zeros off window closers; `did_collect` marks real ones).

    `pre_fn` is the lane-event plumbing for continuous batching
    (docs/serving.md): when given, both runners take an extra per-step
    event pytree `exs` (leading axis T, like xs) and apply
    `pre_fn(state, exs[t])` BEFORE the step at every window-ENTRY clock
    (step % every == 0) — the serving contract that lane events
    (free / admit / re-parameterize) resolve at window boundaries,
    inside the same single dispatch. Event slices at non-entry steps are
    ignored. The aligned shape applies pre_fn statically at each
    window's first step; the generic shape gates it on a per-step
    `lax.cond`, which breaks XLA's in-place carry aliasing on CPU
    (docs/allocator.md) — it remains the semantics reference; drive
    event windows through the aligned shape."""
    every = int(every)

    # -- generic shape: per-step cond ---------------------------------------
    def step_body(carry, xs):
        state, step = carry
        if pre_fn is not None:
            xs, exs = xs
            state = jax.lax.cond(step % every == 0,
                                 lambda s: pre_fn(s, exs),
                                 lambda s: s, state)
        state, out = step_fn(state, xs)
        step = step + 1
        if enabled:
            if overlap:
                state = jax.lax.cond(step % every == every - 1,
                                     arm_fn, lambda s: s, state)
            state, report = jax.lax.cond(
                step % every == 0, collect_fn,
                lambda s: (s, zero_report_fn()), state)
        else:
            report = zero_report_fn()
        return (state, step), {"out": out, "report": report}

    def run_generic(state, xs, step0, exs=None):
        step0 = jnp.asarray(step0, jnp.int32)
        if pre_fn is not None:
            xs = (xs, exs)
        (state, _), ys = jax.lax.scan(step_body, (state, step0), xs)
        return state, ys["out"], ys["report"]

    # -- window-aligned shape: cond-free ------------------------------------
    def window_body(state, wxs):
        if pre_fn is not None:
            wxs, wexs = wxs
            state = pre_fn(state, jax.tree.map(lambda v: v[0], wexs))
        if every > 1:
            head = jax.tree.map(lambda v: v[:every - 1], wxs)
            state, outs = jax.lax.scan(step_fn, state, head)
            # arm fires AFTER step every-1 (the generic path's
            # step % every == every-1 check runs post-step)
            if enabled and overlap:
                state = arm_fn(state)
        last = jax.tree.map(lambda v: v[every - 1], wxs)
        state, out_last = step_fn(state, last)
        if every == 1 and enabled and overlap:
            # degenerate cadence: every step is both the arming and the
            # closing step, and the generic path arms post-step
            state = arm_fn(state)
        if enabled:
            state, report = collect_fn(state)
        else:
            report = zero_report_fn()
        if every > 1:
            outs = jax.tree.map(
                lambda a, b: jnp.concatenate([a, b[None]], axis=0),
                outs, out_last)
        else:
            outs = jax.tree.map(lambda b: b[None], out_last)
        return state, {"out": outs, "report": report}

    def run_aligned(state, xs, exs=None):
        t = jax.tree.leaves(xs)[0].shape[0]

        def to_windows(tree):
            return jax.tree.map(
                lambda v: v.reshape((t // every, every) + v.shape[1:]),
                tree)
        wxs = to_windows(xs)
        if pre_fn is not None:
            wxs = (wxs, to_windows(exs))
        state, ys = jax.lax.scan(window_body, state, wxs)
        outs = jax.tree.map(lambda v: v.reshape((t,) + v.shape[2:]),
                            ys["out"])
        # scatter the per-window reports into the per-step layout the
        # generic shape produces (zeros except at window closers)
        reports = jax.tree.map(
            lambda z, w: jnp.broadcast_to(
                z, (t,) + z.shape).at[every - 1::every].set(w),
            zero_report_fn(), ys["report"])
        return state, outs, reports

    return run_generic, run_aligned


# ---------------------------------------------------------------------------
# fused window — the whole access->collect->backend loop in one dispatch
# ---------------------------------------------------------------------------
def _op_step(pool_cfg: pl.PoolConfig, state: Dict, xs: Dict
             ) -> Tuple[Dict, jax.Array]:
    """Apply one traced op batch (the scan body's op dispatch).

    This is `pool.apply_op` with the TRACED op code — one branch-free
    program per step, not a `lax.switch` over four per-op branches: XLA
    cannot alias a scan carry in place through a conditional whose
    branches update different buffers, so a switch silently re-copied
    the whole heap (`data`) every step, making per-op cost O(n_slots).
    The mask-parameterized op keeps it O(K)."""
    state, vals = pl.apply_op(pool_cfg, state, xs["op"], xs["ids"],
                              xs["values"])
    return state, vals.astype(xs["values"].dtype)


def make_run_window(pool_cfg: pl.PoolConfig, opts: EngineOptions):
    """Build the jitted window programs. The returned
    run(state, trace, step0) -> (state, outs [T,K,W], reports {[T]...})
    dispatches ONE device program for the whole trace.

    Two compiled shapes exist behind the same signature:

      * window-aligned (T % collect_every == 0 and step0 % collect_every
        == 0, the production case): an outer scan over whole windows —
        inner cond-FREE scan over the first every-1 ops, then statically
        arm (if overlapping), apply the window-closing op, and run
        collect+backend. No `lax.cond` anywhere (a per-step cond costs
        real time on CPU), collect work appears once per window.
      * generic (any T/step0): per-step scan with a cond-gated collect —
        the semantics reference for arbitrary clock offsets.

    Reports always come back per-STEP (zeros on non-collect steps,
    `did_collect` marks window closers) so both shapes look identical to
    callers; `step0` is the op-clock value BEFORE the trace, keeping the
    cadence aligned across successive calls."""
    col_cfg = opts.collector
    backend = be.as_backend(opts.backend)
    every = int(opts.collect_every)
    cab = functools.partial(collect_and_backend, pool_cfg, col_cfg, backend)
    run_generic, run_aligned = window_program(
        functools.partial(_op_step, pool_cfg), cab, col.arm,
        every=every, enabled=opts.enabled, overlap=opts.overlap_collect)

    # donate the pool state: the window updates it in place instead of
    # double-buffering the whole pool (notably `data`,
    # (n_slots+1) x slot_words) on every dispatch. Callers must treat the
    # state they pass in as CONSUMED — reuse raises a deleted-buffer
    # error (tests/test_donation.py)
    jit_generic = jax.jit(run_generic, donate_argnums=(0,))
    jit_aligned = jax.jit(run_aligned, donate_argnums=(0,))

    def run(state, trace, step0=0):
        t = int(trace["op"].shape[0])
        if (isinstance(step0, int) and step0 % every == 0
                and t % every == 0 and t > 0):
            return jit_aligned(state, trace)
        return jit_generic(state, trace, step0)

    return run


def make_trace(pool_cfg: pl.PoolConfig,
               steps: Sequence[Tuple[str, jax.Array, Optional[jax.Array]]],
               *, k: Optional[int] = None) -> Dict[str, jax.Array]:
    """Pack a Python list of (op, ids, values_or_None) into the stacked
    fixed-shape trace `run_window` scans over. Each step's ids are padded
    to `k` with -1 (all pool ops drop negative ids); values are padded
    with zeros and cast to the pool dtype."""
    import numpy as np
    if k is None:
        k = max([1] + [len(np.atleast_1d(ids)) for _, ids, _ in steps])
    w = pool_cfg.slot_words
    dtype = jnp.dtype(pool_cfg.dtype)
    t = len(steps)
    op_a = np.zeros((t,), np.int32)
    ids_a = np.full((t, k), -1, np.int32)
    val_a = np.zeros((t, k, w), dtype)
    for i, (op, ids, values) in enumerate(steps):
        op_a[i] = OP_CODES[op]
        ids = np.atleast_1d(np.asarray(ids, np.int32))
        assert len(ids) <= k, f"step {i}: {len(ids)} ops > k={k}"
        ids_a[i, :len(ids)] = ids
        if values is not None:
            val_a[i, :len(ids)] = np.asarray(values, dtype).reshape(-1, w)
    return {"op": jnp.asarray(op_a), "ids": jnp.asarray(ids_a),
            "values": jnp.asarray(val_a)}


def window_reports(reports: Dict[str, jax.Array]) -> List[Dict[str, float]]:
    """Host-side extraction of the real collect reports from a window's
    stacked per-step report pytree (the only place a sync happens)."""
    import numpy as np
    host = {kk: np.asarray(v) for kk, v in reports.items()}
    out = []
    for i in np.nonzero(host["did_collect"])[0]:
        out.append({kk: float(v[i]) for kk, v in host.items()})
    return out


class Engine:
    """Holds the compiled entry points for one pool geometry + options.

    `run_window` / `serve_steps` are the production path (one dispatch per
    window); `step` is the per-op compatibility path the `Hades` wrapper
    uses (one dispatch per op, collect fused into the op that closes the
    window)."""

    def __init__(self, pool_cfg: pl.PoolConfig,
                 opts: Optional[EngineOptions] = None):
        self.cfg = pool_cfg
        self.opts = opts or EngineOptions()
        self.backend = be.as_backend(self.opts.backend)
        self._run = make_run_window(pool_cfg, self.opts)
        # every entry point donates the incoming pool state (in-place
        # window updates; see make_run_window)
        self._apply = jax.jit(
            functools.partial(apply_step, pool_cfg, self.opts.collector,
                              self.backend),
            static_argnames=("op", "do_arm", "do_collect"),
            donate_argnums=(0,))
        self._collect = jax.jit(functools.partial(
            collect_and_backend, pool_cfg, self.opts.collector,
            self.backend), donate_argnums=(0,))

    def init(self) -> Dict:
        """Fresh pool state, with the backend's carried state seeded in
        (`bstate` rides the window-scan carry from here on)."""
        return dict(pl.init(self.cfg), bstate=self.backend.init(self.cfg))

    # -- fused path ---------------------------------------------------------
    def run_window(self, state: Dict, trace: Dict[str, jax.Array],
                   step0: int = 0):
        """Execute `trace` (any number of steps/windows) as ONE dispatch.
        `state` is DONATED: the pool updates in place and the passed-in
        pytree must not be used again (keep the returned state)."""
        return self._run(state, trace, step0)

    def serve_steps(self, state: Dict, trace: Dict[str, jax.Array],
                    *, step0: int = 0, window: Optional[int] = None):
        """Stream `trace` window-by-window (`window` steps per dispatch,
        default `collect_every`) so reports can be consumed between
        dispatches. Returns (state, outs [T,K,W], reports list). The
        incoming `state` is donated to the first window's dispatch and
        each window's output state is donated to the next — the pool is
        never double-buffered across the stream."""
        t = trace["op"].shape[0]
        window = window or self.opts.collect_every
        outs, reps = [], []
        for lo in range(0, t, window):
            chunk = {kk: v[lo:lo + window] for kk, v in trace.items()}
            state, out, rep = self._run(state, chunk, step0 + lo)
            outs.append(out)
            reps.extend(window_reports(rep))
        if not outs:               # empty trace: clean no-op
            return state, jnp.zeros_like(trace["values"]), reps
        return state, jnp.concatenate(outs, axis=0), reps

    # -- per-op compatibility path ------------------------------------------
    def step(self, state: Dict, op: str, ids, values=None, *,
             do_arm: bool = False, do_collect: bool = False):
        ids = jnp.asarray(ids, jnp.int32)
        if values is not None:
            values = jnp.asarray(values)
        return self._apply(state, ids, values, op=op, do_arm=do_arm,
                           do_collect=do_collect)

    def collect_now(self, state: Dict):
        return self._collect(state)
