"""Kernel micro-benchmarks: the 5 Pallas kernels vs their jnp oracles.

NOTE on semantics: this container is CPU-only, so Pallas runs in
INTERPRET mode — wall times here validate plumbing cost, not TPU
performance (TPU perf is the §Roofline analysis). The oracle timing is
the XLA:CPU fused path; the derived column reports bytes touched so the
numbers can be sanity-checked against any machine's bandwidth.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, timed
from repro.kernels import ops, ref


def main(smoke: bool = False):
    rng = np.random.default_rng(0)
    s = 2 if smoke else 1

    # migrate
    data = jnp.asarray(rng.normal(size=(1024 // s, 256)).astype(np.float32))
    n_mv = 128 // s
    src = jnp.asarray(rng.choice(512 // s, n_mv, replace=False), jnp.int32)
    dst = jnp.asarray(512 // s + rng.choice(512 // s, n_mv, replace=False),
                      jnp.int32)
    ok = jnp.ones(n_mv, bool)
    us = timed(lambda: ops.migrate(data, src, dst, ok))
    us_ref = timed(lambda: ref.migrate(data, src, dst, ok))
    emit("kernel_migrate", us,
         f"ref_us={us_ref:.0f};moved_kib={n_mv*256*4/1024:.0f}")

    # access_scan
    from repro.core import object_table as ot
    n = 4096 // s
    tbl = ot.pack(jnp.arange(n, dtype=jnp.uint32) % 1024,
                  jnp.asarray(rng.integers(0, 3, n), jnp.uint32),
                  jnp.asarray(rng.integers(0, 2, n), jnp.uint32))
    ct = jnp.asarray(3, jnp.uint32)
    us = timed(lambda: ops.access_scan(tbl, ct, sb_slots=64, n_sbs=16))
    us_ref = timed(lambda: ref.access_scan(tbl, ct, 64, 16))
    emit("kernel_access_scan", us, f"ref_us={us_ref:.0f};objects={n}")

    # flash attention
    b, sq, h, kv, d = 1, 512 // s, 8, 2, 64
    q = jnp.asarray(rng.normal(size=(b, sq, h, d)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(b, sq, kv, d)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(b, sq, kv, d)).astype(np.float32))
    us = timed(lambda: ops.flash_attention(q, k, v))
    us_ref = timed(lambda: ref.flash_attention(q, k, v))
    flops = 4 * b * h * sq * sq * d // 2
    emit("kernel_flash_attention", us,
         f"ref_us={us_ref:.0f};mflops={flops/1e6:.0f}")

    # paged attention
    n_slots, bt, mb = 64, 16, 8
    q1 = jnp.asarray(rng.normal(size=(4, h, d)).astype(np.float32))
    kvp = jnp.asarray(rng.normal(size=(n_slots, 2, kv, bt, d))
                      .astype(np.float32))
    tables = jnp.asarray(rng.integers(0, n_slots, (4, mb)), jnp.int32)
    lens = jnp.full((4,), bt * mb, jnp.int32)
    us = timed(lambda: ops.paged_attention(q1, kvp, tables, lens))
    us_ref = timed(lambda: ref.paged_attention(q1, kvp, tables, lens))
    emit("kernel_paged_attention", us,
         f"ref_us={us_ref:.0f};kv_kib={4*mb*bt*kv*d*2*4/1024:.0f}")

    # mamba scan
    a = jnp.asarray(rng.uniform(0.5, 1, (2, 256 // s, 16, 16))
                    .astype(np.float32))
    bb = jnp.asarray(rng.normal(size=(2, 256 // s, 16, 16))
                     .astype(np.float32))
    h0 = jnp.zeros((2, 16, 16), jnp.float32)
    us = timed(lambda: ops.mamba_scan(a, bb, h0))
    us_ref = timed(lambda: ref.mamba_scan(a, bb, h0))
    emit("kernel_mamba_scan", us,
         f"ref_us={us_ref:.0f};state_kib={2*16*16*4/1024:.1f}")


if __name__ == "__main__":
    main()
