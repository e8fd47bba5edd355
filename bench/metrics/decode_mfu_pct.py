"""The whole serving step's share of the chip's bf16 peak over the traced
stretch: model FLOPs of the tokens that active lanes processed for their
requests (prompt tokens fed, and generated tokens kept; idle lanes and
post-finish overshoot left out), each at its own context length by the
family's `token_flops`, over the stretch's length and the peak."""


def read(r):
    if r.trace is None or r.peak is None:
        return None
    job, first, end = r.traced
    flops = sum(r.costs.token_flops(r.shapes, pos + 1)
                for _, pos, useful in r.jobs[job].lane_steps(first, end)
                if useful)
    return 100.0 * flops / (r.trace.window_s * r.peak.bf16_flops)
