"""The Pallas paged_attention kernel's share of its roofline over the
traced stretch. Least time: for each layer of each step the kernel
served, that layer call's FLOPs and bytes summed over its lanes (the
family's `attention_layers`), and the larger of the bytes over HBM
bandwidth and the FLOPs over the bf16 peak, summed over every layer call.
A lane's bytes are its live KV blocks in that layer (what the live-bytes
gauge counts, or fewer where a layer reads only a window) plus q and
out, so blocks that hold no live token are not counted and a kernel that
skips them reads the same work. Memory bounds every GLM call at these
shapes (4 FLOPs per 2 bytes of KV). Kernel time: the summed device
durations of the ops named after it."""
from collections import defaultdict

import numpy as np


def read(r):
    if r.trace is None or r.peak is None:
        return None
    kernel_s = r.trace.kernel_s.get("paged_attention", 0.0)
    if kernel_s <= 0:
        return None
    job, first, end = r.traced
    calls = defaultdict(int)       # step -> [layers, 2] of (FLOPs, bytes)
    for step, pos, _ in r.jobs[job].lane_steps(first, end):
        calls[step] = calls[step] + np.array(
            r.costs.attention_layers(r.shapes, pos + 1, r.block_tokens),
            dtype=np.float64)
    least = sum(float(np.maximum(c[:, 1] / r.peak.hbm_bytes_per_s,
                                 c[:, 0] / r.peak.bf16_flops).sum())
                for c in calls.values())
    return 100.0 * least / kernel_s
