"""Top-k picks served per held expert whose weights an MoE layer read:
the program's per-window counters in `serve_log`, summed over every
window of the window's jobs, `expert_picks` (picks of active lanes that
landed on a held expert, over steps and MoE layers) over `experts_read`
(held experts read, over steps and MoE layers). The drop-free expert
layer reads every held expert each step, so this is the work each
expert's weight stream carries; it rises with busier lanes and would with
a layer that skips experts nobody picked. Silent where the program keeps
no such records."""


def read(r):
    log = [e for j in r.jobs for e in j.serve_log]
    if not log or not all("expert_picks" in e for e in log):
        return None
    read_ = sum(e["experts_read"] for e in log)
    return sum(e["expert_picks"] for e in log) / read_ if read_ else None
