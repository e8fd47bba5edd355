"""Records the spans in which JAX traces, lowers and compiles (a cache
load included), from JAX's own monitoring events, so that set-up can be
told apart from the measured window and a compile inside the window is
caught. Traces nest (a jitted function traced inside another), so the
compile time in an interval is the union of the spans in it. JAX stamps
the spans with `time.time()`.
"""
from __future__ import annotations

import jax


class CompileClock:
    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.spans = []
        jax.monitoring.register_event_time_span_listener(self._on)

    def _on(self, event, start, end, **_):
        if event in self.EVENTS:
            self.spans.append((start, end))

    def seconds(self, t0: float, t1: float) -> float:
        """Length of the union of the recorded spans within [t0, t1]."""
        total, reach = 0.0, t0
        for start, end in sorted(self.spans):
            start, end = max(start, reach), min(end, t1)
            if end > start:
                total += end - start
                reach = end
        return total

    def count(self, t0: float, t1: float) -> int:
        """Spans that overlap [t0, t1]."""
        return sum(1 for s, e in self.spans if e > t0 and s < t1)
