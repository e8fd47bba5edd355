"""Share of the lane-steps the window program ran that did useful work:
the program's per-window counters in `serve_log`, summed over every
window of the window's jobs, `useful_lane_steps` (a prompt token fed or a
kept token produced) over `lane_steps` (lanes x window steps). Idle
lanes and the steps a finished lane runs to its window's end are the
rest. Silent where the program keeps no such counters."""


def read(r):
    log = [e for j in r.jobs for e in j.serve_log]
    if not log or not all("lane_steps" in e for e in log):
        return None
    return 100.0 * sum(e["useful_lane_steps"] for e in log) \
        / sum(e["lane_steps"] for e in log)
