"""One run of one cell: set-up, the measured window of identical batch
jobs through the program's `Server.serve`, the output check, and the
metrics. `run.py` is the command line around `execute`.

Everything that belongs to one configuration, traffic mix or per-layer
metric is found by name: `configs/<config>.json`, `traffic/<mix>.json`,
`metrics/<metric>.py`, `cells/<workload>.json`. A configuration file's
`family` names three modules under `models/`:

- `<family>.py`: the program's side, `model_config(name, shapes)` and
  `program_params(shapes, key)`;
- `<family>_reference.py`: the plain reference, `Shapes`,
  `shapes(config)`, `seed_key(seed)`, `weights(shapes, key)` and
  `gaps(shapes, weights, tokens, targets, quant=None)`;
- `<family>_costs.py`: the work the per-layer readers price,
  `token_flops(shapes, ctx)` (model FLOPs of one token attending to `ctx`
  positions, over every layer and the head) and
  `attention_layers(shapes, ctx, block_tokens)` (one `(flops, bytes)` per
  layer for one lane's decode attention: its live KV blocks, q in and out).

A family that lacks one of them fails when its cell is resolved.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import check, traffic, trace_reduce

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
BLOCK_TOKENS = 16           # KV block, tokens
WINDOW = 16                 # decode steps per serving window (one dispatch)
TRACE_FROM, TRACE_WINDOWS = 4, 4   # the traced stretch: windows [4, 8)


# ---------------------------------------------------------------------------
# the cell, from BENCHMARK.json and the files it names
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    mix: dict
    family: object             # models/<family>.py
    reference: object          # models/<family>_reference.py
    costs: object              # models/<family>_costs.py
    shapes: object             # reference.Shapes
    end_to_end: List[dict]
    per_layer: List[dict]


def resolve(workload: str, root: Path = ROOT) -> Cell:
    with open(root / "BENCHMARK.json") as f:
        bench = json.load(f)
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    c = cells[workload]
    entry = {x["name"]: x for x in bench["configs"]}[c["config"]]
    with open(root / entry["file"]) as f:
        config = json.load(f)
    family, reference, costs = (
        importlib.import_module(f"bench.models.{config['family']}{part}")
        for part in ("", "_reference", "_costs"))

    def mine(m):
        return "workloads" not in m or workload in m["workloads"]
    return Cell(
        name=workload, chips=c["chips"], config_name=c["config"],
        mix=traffic.load(c["traffic"]), family=family,
        reference=reference, costs=costs, shapes=reference.shapes(config),
        end_to_end=[m for m in bench["end_to_end"] if mine(m)],
        per_layer=[m for m in bench["per_layer"] if mine(m)])


def reader(metric: str):
    """The `read(readout)` function of metrics/<metric>.py."""
    path = HERE / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# the window: identical jobs through Server.serve
# ---------------------------------------------------------------------------
class Dispatches:
    """Wraps the server instance's window program — the one dispatch per
    window that `serve` makes — to stamp each dispatch's host time, and to
    start and stop the profiler around a stretch of dispatches. The
    program has no public per-window hook yet; if the attribute is gone
    this fails rather than time anything else."""

    def __init__(self, srv):
        if not callable(getattr(srv, "_win_serve", None)):
            raise RuntimeError("Server has no _win_serve window program to "
                               "time; the benchmark needs its dispatches")
        self.fn = srv._win_serve
        self.times: List[float] = []
        self.trace = None          # (first, end, directory) to profile
        self._span = None
        srv._win_serve = self

    def __call__(self, *args, **kwargs):
        k = len(self.times)
        if self.trace is not None:
            first, end, where = self.trace
            if k == first:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(where, profiler_options=opts)
                self._span = jax.profiler.TraceAnnotation(trace_reduce.SPAN)
                self._span.__enter__()
            elif k == end and self._span is not None:
                self.stop()
        self.times.append(time.perf_counter())
        with jax.profiler.TraceAnnotation("dispatch"):
            return self.fn(*args, **kwargs)

    def stop(self):
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None
            jax.profiler.stop_trace()
            self.trace = None


def annotate(obj, attr: str, name: str):
    """Wrap a bound method of `obj` in a host trace annotation."""
    fn = getattr(obj, attr)

    def wrapped(*a, **k):
        with jax.profiler.TraceAnnotation(name):
            return fn(*a, **k)
    setattr(obj, attr, wrapped)


@dataclasses.dataclass
class Job:
    specs: list
    t0: float
    t1: float
    done: list
    dispatch_t: List[float]
    serve_log: List[dict]
    reports: List[dict]

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def intervals(self) -> List[float]:
        """Host seconds from each window's dispatch to the next: the
        window's device time and the host's gap after it, together."""
        t = self.dispatch_t
        return [b - a for a, b in zip(t, t[1:])]

    def latencies(self) -> List[float]:
        """Each request's completion time less its job's start: a request
        freed at the entry of window f had its last token on the host
        when dispatch f was issued (or when `serve` returned)."""
        out = []
        for c in self.done:
            f = c.windows[1]
            t = self.dispatch_t[f] if f < len(self.dispatch_t) else self.t1
            out.append(t - self.t0)
        return out

    def lane_steps(self, first: int = 0, end: Optional[int] = None):
        """(step, position, useful) of every lane-step the window program ran
        for a request in windows [first, end): the lane is active from its
        admitting window to the window that frees it; a step is useful
        while it feeds a prompt or a kept token (not post-finish
        overshoot)."""
        end = len(self.dispatch_t) if end is None else end
        for spec, c in zip(self.specs, self.done):
            a, f = c.windows
            need = len(spec.prompt) + len(c.tokens) - 1
            lo, hi = max(a, first) * WINDOW, min(f, end) * WINDOW
            for g in range(lo, hi):
                pos = g - a * WINDOW
                yield g, pos, pos < need


@dataclasses.dataclass
class Readout:
    """What the per-layer readers read."""
    shapes: object
    costs: object                  # the family's models/<family>_costs.py
    peak: object
    block_tokens: int
    jobs: List[Job]
    trace: Optional[trace_reduce.Summary]
    traced: Optional[tuple]        # (job, first window, end window)


def _requests(specs):
    from repro.runtime.server import Request
    return [Request(prompt=s.prompt, max_new=s.max_new) for s in specs]


def run_job(srv, params, specs, disp: Dispatches) -> Job:
    disp.times = []
    reqs = _requests(specs)
    with jax.profiler.TraceAnnotation("job"):
        t0 = time.perf_counter()
        done = srv.serve(params, reqs)
        t1 = time.perf_counter()
    return Job(specs, t0, t1, done, list(disp.times), list(srv.serve_log),
               list(srv.reports))


def job_count(seconds: float, job: Job) -> int:
    """How many identical jobs a window of `seconds` measures: the whole
    number nearest to `seconds` over the job's length, at least one. The
    length is the job's dispatches times their median interval, so that a
    stall or the profiler's stop in a few windows cannot change the count,
    and rounding puts the edges between counts at 2/3, 2/5, 2/7... of
    `seconds` rather than at its halves and thirds."""
    length = len(job.dispatch_t) * float(np.median(job.intervals()))
    return max(1, round(seconds / length))


def build(cell: Cell, seed: int):
    """The program under test at the cell's sizes, and its weights."""
    from repro.models.model import Model
    from repro.runtime.server import Server, ServerConfig
    mc = cell.family.model_config(cell.config_name, cell.shapes)
    params = cell.family.program_params(cell.shapes,
                                        cell.reference.seed_key(seed))
    srv = Server(Model(mc), ServerConfig(
        batch=cell.mix["lanes"], max_len=cell.mix["max_len"],
        block_tokens=BLOCK_TOKENS, window=WINDOW,
        # an id no vocabulary id matches: each request runs to max_new
        eos_token=-1))
    return srv, jax.block_until_ready(params)


def execute(cell: Cell, seed: int, seconds: float, trace: bool, clock,
            process_start: float, peak=None):
    """Set-up, window, check and metrics of one run; returns the result
    line's fields. `peak` is the chip's peak table entry (None off-chip,
    where no device metric is computed). The trace goes to a temporary
    directory that is removed once it is read."""
    vocab = cell.shapes.vocab
    t0 = time.time()
    srv, params = build(cell, seed)
    disp = Dispatches(srv)
    annotate(srv, "kv_rss_bytes", "gauge_rss")
    annotate(srv, "kv_live_bytes", "gauge_live")
    annotate(srv, "_window_events", "events")
    t1 = time.time()
    run_job(srv, params, traffic.warmup(cell.mix, vocab), disp)
    specs = traffic.job(cell.mix, vocab, seed)
    t2 = time.time()
    print(f"setup: start {t0 - process_start:.3f} s, weights and server "
          f"{t1 - t0:.3f} s (compile {clock.seconds(t0, t1):.3f}), warm-up "
          f"{t2 - t1:.3f} s (compile {clock.seconds(t1, t2):.3f})",
          file=sys.stderr, flush=True)

    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        disp.trace = (TRACE_FROM, TRACE_FROM + TRACE_WINDOWS, trace_dir)
    w0 = time.time()
    setup_s = w0 - process_start
    jobs = [run_job(srv, params, specs, disp)]
    disp.stop()
    for _ in range(job_count(seconds, jobs[0]) - 1):
        jobs.append(run_job(srv, params, specs, disp))
    w1 = time.time()
    compiles = clock.count(w0, w1)
    if compiles:
        raise RuntimeError(f"{compiles} compilations inside the measured "
                           f"window ({clock.seconds(w0, w1):.3f} s)")
    stats = jax.devices()[0].memory_stats() or {}
    memory_peak = stats.get("peak_bytes_in_use")

    # free the program's state before the reference takes the chip
    del srv, params, disp
    gc.collect()
    checks = verify(cell, jobs, seed)
    correct = all(v["value"] <= v["limit"] for v in checks.values())

    summary = traced = None
    if trace:
        summary = trace_reduce.reduce(trace_reduce.find(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        traced = (0, TRACE_FROM, TRACE_FROM + TRACE_WINDOWS)
    readout = Readout(cell.shapes, cell.costs, peak, BLOCK_TOKENS, jobs,
                      summary, traced)
    e2e = end_to_end(jobs, setup_s)
    if trace:
        metrics = {}
        for m in cell.per_layer:
            v = reader(m["name"])(readout)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    attempted = sum(len(j.specs) for j in jobs)
    return {
        "correct": correct,
        "attempted": attempted,
        "failed": int(checks["bad_completions"]["value"]),
        "metrics": metrics,
        "memory_peak_bytes": memory_peak,
        "summary": summary,
        "checks": checks,
        "jobs": jobs,
    }


def end_to_end(jobs: List[Job], setup_s: float) -> dict:
    """Tokens over the whole window, from the first job's start to the last
    job's end, and the tail of every request's latency."""
    tokens = sum(len(c.tokens) for j in jobs for c in j.done)
    lat = [x for j in jobs for x in j.latencies()]
    return {
        "out_tok_per_s": tokens / (jobs[-1].t1 - jobs[0].t0),
        "req_latency_p95_s": float(np.percentile(lat, 95)),
        "setup_s": setup_s,
    }


def verify(cell: Cell, jobs: List[Job], seed: int) -> dict:
    """The numbers compared, each with its limit (see check.py)."""
    bad = sum(check.bad_completions(j.specs, j.done, cell.shapes.vocab)
              for j in jobs)
    limit = check.limits(cell.name)["max_logit_gap"]["limit"]
    pairs = check.sample(jobs, cell.mix["check_requests"], seed)
    tokens, targets = check.rows(pairs, jobs, check.row_length(cell.mix))
    ref = cell.reference
    w = ref.weights(cell.shapes, ref.seed_key(seed))
    g = np.asarray(ref.gaps(cell.shapes, w, jnp.asarray(tokens),
                            jnp.asarray(targets)))
    return {
        "bad_completions": {"value": bad, "limit": 0},
        "max_logit_gap": {"value": float(g.max()), "limit": limit},
    }

