"""From a profiler trace (`.xplane.pb`) to the numbers the per-layer
metrics and the breakdown read.

The traced stretch is the host span `SPAN` that the harness opens and
closes around a run of serving windows. Within it, on the device plane's
"XLA Ops" line, where ops nest (a window's `while` loop holds the ops of
its body):
- busy: the union of the ops' intervals;
- kernel time: the summed durations of the ops whose name (the HLO
  instruction name, `paged_attention.24`) starts with a kernel's name;
- top ops: self time (duration less that of the ops nested in it) by HLO
  instruction name;
- idle gaps: the stretches with no device op, each put to the innermost
  of the harness's host spans (`dispatch`, `events`, `gauge_rss`,
  `gauge_live`) that covers its midpoint, or else to `serve_loop` (the
  host work of `Server.serve` itself: lane bookkeeping, the token and
  report syncs), summed by that name.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from collections import defaultdict
from typing import Dict, List, Tuple

SPAN = "traced"
HOST_SPANS = ("dispatch", "events", "gauge_rss", "gauge_live")
OTHER_HOST = "serve_loop"
KERNELS = ("paged_attention", "migrate", "access_scan")
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    kernel_s: Dict[str, float]
    top_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]
    n_ops: int


def find(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {paths}")
    return paths[0]


def op_name(event_name: str) -> str:
    """`%paged_attention.24 = (...) custom-call(...)` -> `paged_attention.24`"""
    return event_name.split(" = ", 1)[0].lstrip("%")


def load(data: bytes):
    """(device op events, host span events) as (name, start_ns, end_ns)."""
    from jax.profiler import ProfileData
    xspace = ProfileData.from_serialized_xspace(data)
    ops, host = [], []
    for plane in xspace.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((op_name(e.name), e.start_ns, e.end_ns)
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.end_ns)
                            for e in line.events
                            if e.name in HOST_SPANS or e.name == SPAN)
    return ops, host


def _self_times(ops):
    """Self time of each op on a line where ops nest: its duration less
    the durations of the ops directly inside it."""
    out: Dict[str, float] = defaultdict(float)
    stack = []                                   # [name, end, child_ns]
    for name, s, e in sorted(ops, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            n, _, child = stack.pop()
            out[n] -= child
        if stack:
            stack[-1][2] += e - s
        out[name] += e - s
        stack.append([name, e, 0])
    for n, _, child in stack:
        out[n] -= child
    return out


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def reduce(path: str, top: int = 10) -> Summary:
    with open(path, "rb") as f:
        return reduce_events(*load(f.read()), top=top)


def reduce_events(ops, host, top: int = 10) -> Summary:
    spans = [(s, e) for n, s, e in host if n == SPAN]
    if len(spans) != 1:
        raise RuntimeError(f"expected one {SPAN!r} host span, got {spans}")
    w0, w1 = spans[0]
    inside = [(n, max(s, w0), min(e, w1)) for n, s, e in ops
              if e > w0 and s < w1]
    if not inside:
        raise RuntimeError("no device op inside the traced stretch")
    busy = _union([(s, e) for _, s, e in inside])
    kernel_ns: Dict[str, float] = defaultdict(float)
    for n, s, e in inside:
        for k in KERNELS:
            if n.startswith(k):
                kernel_ns[k] += e - s
    self_ns = _self_times(inside)

    gaps, reach = [], w0
    for s, e in busy:
        if s > reach:
            gaps.append((reach, s))
        reach = max(reach, e)
    if w1 > reach:
        gaps.append((reach, w1))
    named = [(n, s, e) for n, s, e in host if n in HOST_SPANS]
    idle: Dict[str, float] = defaultdict(float)
    for s, e in gaps:
        mid = (s + e) / 2
        cover = [(e2 - s2, n) for n, s2, e2 in named if s2 <= mid <= e2]
        idle[min(cover)[1] if cover else OTHER_HOST] += (e - s) * 1e-9
    rank = sorted(self_ns.items(), key=lambda kv: -kv[1])[:top]
    return Summary(
        window_s=(w1 - w0) * 1e-9,
        busy_s=sum(e - s for s, e in busy) * 1e-9,
        kernel_s={k: v * 1e-9 for k, v in kernel_ns.items()},
        top_ops=[(n, v * 1e-9) for n, v in rank],
        idle_gaps=sorted(idle.items(), key=lambda kv: -kv[1])[:top],
        n_ops=len(inside))
