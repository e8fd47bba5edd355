"""Host milliseconds per window in which the chip has nothing queued: the
median, over every window of the window's jobs but each job's last, of
the time from the host first holding the window's outputs (`t_ready` in
the program's `serve_log`) to the next window's dispatch (`t_dispatch`).
Silent where the program keeps no such stamps."""
import statistics


def read(r):
    gaps = []
    for j in r.jobs:
        log = j.serve_log
        if not all("t_ready" in e and "t_dispatch" in e for e in log):
            return None
        gaps += [b["t_dispatch"] - a["t_ready"] for a, b in zip(log, log[1:])]
    if not gaps:
        return None
    return 1000.0 * statistics.median(gaps)
