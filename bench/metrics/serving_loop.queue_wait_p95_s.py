"""Seconds a request waits for a lane: the 95th percentile (numpy's
linear interpolation) over every request of the window's jobs of its
admitting window's dispatch (`Completion.t_admitted`, the program's
stamp) less its job's start, on the same clock. In these closed batches
every request is due at its job's start.

The harness starts and stops the profiler inside the traced job's window
dispatches, between the program's stamp of a dispatch (`serve_log`'s
`t_dispatch`) and its own (`Job.dispatch_t`); that time, in the windows
before the admitting one, is the benchmark's and is left out of the wait.
Silent where the program keeps no such stamps."""
import numpy as np


def read(r):
    waits = []
    for j in r.jobs:
        mine = [e.get("t_dispatch") for e in j.serve_log]
        if None in mine or len(mine) != len(j.dispatch_t):
            return None
        harness = np.cumsum([0.0] + list(np.subtract(j.dispatch_t, mine)))
        for c in j.done:
            if not hasattr(c, "t_admitted"):
                return None
            waits.append(c.t_admitted - j.t0 - harness[c.windows[0]])
    if not waits:
        return None
    return float(np.percentile(waits, 95))
