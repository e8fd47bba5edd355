"""Host milliseconds per decode step, the median over every window of the
window's jobs: the time from one window's dispatch to the next, over the
window's steps. A stall of the host lands in one window and leaves the
median where it was, so this reads a change of the step that the rate's
noise would hide."""
import statistics

from bench import harness


def read(r):
    iv = [x for j in r.jobs for x in j.intervals()]
    if not iv:
        return None
    return 1000.0 * statistics.median(iv) / harness.WINDOW
