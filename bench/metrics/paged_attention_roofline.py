"""The Pallas paged_attention kernel's share of its roofline over the
traced stretch. Least time: for each layer of each lane-step the kernel
served, the larger of the bytes it needs over HBM bandwidth and its FLOPs
over the bf16 peak, summed over the lanes of that call; the bytes are the live KV blocks of that lane (what
the live-bytes gauge counts) plus q and out, so blocks that hold no live
token are not counted and a kernel that skips them reads the same work.
Memory bounds every call at these shapes (4 FLOPs per 2 bytes of KV).
Kernel time: the summed device durations of the ops named after it."""
from collections import defaultdict

from bench import costs


def read(r):
    if r.trace is None or r.peak is None:
        return None
    kernel_s = r.trace.kernel_s.get("paged_attention", 0.0)
    if kernel_s <= 0:
        return None
    job, first, end = r.traced
    calls = defaultdict(lambda: [0, 0])      # step -> [FLOPs, bytes]
    for step, pos, _ in r.jobs[job].lane_steps(first, end):
        flops, nbytes = costs.attention_call(r.shapes, pos + 1,
                                             r.block_tokens)
        calls[step][0] += flops
        calls[step][1] += nbytes
    least = sum(max(b / r.peak.hbm_bytes_per_s, f / r.peak.bf16_flops)
                for f, b in calls.values())
    return 100.0 * least * r.shapes.layers / kernel_s
