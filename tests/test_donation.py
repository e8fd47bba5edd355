"""Donated window buffers: every window entry point (`Engine.run_window`
/ `serve_steps` / `Engine.step` / the server's decode programs) donates
the incoming pool state, so the pool — notably `data`,
(n_slots+1) x slot_words — is updated in place instead of being
double-buffered per dispatch.

The regression surface is the CALLER contract: a donated state is
consumed, so (a) the framework's own paths (`Hades`, `Engine.step`,
`Server`) must never touch a state after passing it in, (b) streaming
(`serve_steps`, `generate`) must keep working across chained donations,
and (c) an external caller reusing a donated state must fail loudly
(deleted buffer), not read garbage."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import Hades, HadesOptions, make_config
from repro.core import collector as col
from repro.core import engine as eng
from repro.core.backend import BackendConfig

CFG = make_config(max_objects=64, slot_words=8, sb_slots=8, page_slots=4,
                  slack=2.0)


def _opts(every=4):
    return HadesOptions(collect_every=every,
                        backend=BackendConfig(kind="proactive"),
                        collector=col.CollectorConfig())


def _steps(n_objs=32, n_steps=11):
    rng = np.random.default_rng(0)
    vals = rng.normal(size=(n_objs, CFG.slot_words)).astype(np.float32)
    steps = [("alloc", np.arange(n_objs), vals)]
    for _ in range(n_steps):
        steps.append(("read", rng.integers(0, n_objs, 6), None))
    return steps, vals


def test_run_window_consumes_state():
    """The fused window donates its state input: the passed-in pytree is
    deleted (updated in place, not copied) and reuse fails loudly."""
    e = eng.Engine(CFG, _opts())
    steps, _ = _steps()
    trace = eng.make_trace(CFG, steps)
    s0 = e.init()
    s1, _, _ = e.run_window(s0, trace, 0)
    jax.block_until_ready(s1["table"])
    assert all(leaf.is_deleted()
               for leaf in jax.tree_util.tree_leaves(s0)), \
        "donation did not engage: the input pool was copied, not reused"
    with pytest.raises((RuntimeError, ValueError)):
        e.run_window(s0, trace, 0)           # reuse must fail, not alias
    # the returned state is alive and chains into the next window
    s2, _, _ = e.run_window(s1, trace, len(steps))
    assert not any(leaf.is_deleted()
                   for leaf in jax.tree_util.tree_leaves(s2))


def test_hades_per_op_path_never_reuses_donated_state():
    """`Hades`/`Engine.step` reassign their state on every op — a long
    op/collect/metric sequence works and reads back correct payloads."""
    h = Hades(CFG, _opts())
    steps, vals = _steps(n_steps=13)
    for op, ids, values in steps:
        if op == "alloc":
            h.alloc(ids, values)
        else:
            got = h.read(ids)
            assert np.allclose(np.asarray(got), vals[ids])
    h.collect()                               # forced collect_now path
    assert h.rss_bytes() > 0                  # metrics on the live state
    assert h.heap_histogram()["hot"] + h.heap_histogram()["new"] + \
        h.heap_histogram()["cold"] == 32
    got = h.read(np.arange(32))
    assert np.allclose(np.asarray(got), vals)


def test_serve_steps_streams_with_donation():
    """Streaming chains donations window-to-window: results and reports
    are identical to the one-shot scan (each from its own fresh init)."""
    steps, _ = _steps(n_steps=15)
    trace = eng.make_trace(CFG, steps)
    e = eng.Engine(CFG, _opts())
    s1, o1, r1 = e.run_window(e.init(), trace, 0)
    s2, o2, reps = e.serve_steps(e.init(), trace)
    for (path, x), y in zip(
            jax.tree_util.tree_flatten_with_path(s1)[0],
            jax.tree_util.tree_leaves(s2)):
        assert np.array_equal(np.asarray(x), np.asarray(y)), \
            f"state{jax.tree_util.keystr(path)} diverged"
    assert np.array_equal(np.asarray(o1), np.asarray(o2))
    assert len(reps) == len(steps) // 4
    assert all(r["did_collect"] for r in reps)


def test_server_decode_paths_never_reuse_donated_carry():
    """The server's two programs (the per-step reference / the serving
    window) both donate the decode carry; generate streams across
    windows and the previous window's pool buffers are actually
    released."""
    from repro.models.model import build
    from repro.runtime.server import Server, ServerConfig

    m = build("chatglm3-6b", reduced=True)
    params = m.init(jax.random.PRNGKey(0))
    srv = Server(m, ServerConfig(batch=2, max_len=32, block_tokens=4,
                                 collect_every=4))
    rng = np.random.default_rng(1)
    prompts = jnp.asarray(rng.integers(0, m.cfg.vocab_size, (2, 3)),
                          jnp.int32)

    out = srv.generate(params, prompts, max_new=6)
    assert out.shape == (2, 6)
    pool_before = srv.state["pool"]["data"]

    toks = jnp.asarray(rng.integers(0, m.cfg.vocab_size, (2,)), jnp.int32)
    srv.decode_step(params, toks)             # donates the held carry
    assert pool_before.is_deleted(), \
        "decode did not donate the previous pool buffer"
    for _ in range(3):                        # back to a collect boundary
        srv.decode_step(params, toks)
    # a window after the steps still works, and donates the carry too
    pool_before = srv.state["pool"]["data"]
    logits, sampled, _ = srv.decode_window(params, toks, w=4)
    assert pool_before.is_deleted()
    assert bool(jnp.all(jnp.isfinite(logits)))
    assert srv.kv_rss_bytes() > 0
