"""Trainer (resume/preemption/stragglers), checkpoint atomicity,
optimizer convergence, gradient compression."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import ckpt as ckpt_lib
from repro.data.lm import DataConfig
from repro.models.model import build
from repro.optim import adamw, compression
from repro.runtime.trainer import Trainer, TrainerConfig


def test_adamw_converges_on_quadratic():
    cfg = adamw.AdamWConfig(lr=0.1, weight_decay=0.0, warmup_steps=0,
                            total_steps=100)
    params = {"w": jnp.asarray([5.0, -3.0])}
    state = adamw.adamw_init(params)
    for _ in range(100):
        g = jax.grad(lambda p: jnp.sum(p["w"] ** 2))(params)
        params, state, m = adamw.adamw_update(cfg, params, g, state)
    assert float(jnp.abs(params["w"]).max()) < 0.1


def test_grad_clip_bounds_update():
    cfg = adamw.AdamWConfig(lr=1.0, grad_clip=1.0, warmup_steps=0)
    params = {"w": jnp.zeros(4)}
    state = adamw.adamw_init(params)
    g = {"w": jnp.full(4, 1e6)}
    _, _, m = adamw.adamw_update(cfg, params, g, state)
    assert float(m["grad_norm"]) > 1e5          # reported pre-clip


def test_compression_roundtrip_and_error_feedback():
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.normal(size=(1000,)).astype(np.float32))
    q, s = compression.compress_int8(g)
    back = compression.decompress_int8(q, s, g.shape, jnp.float32)
    rel = float(jnp.linalg.norm(back - g) / jnp.linalg.norm(g))
    assert rel < 0.02                           # int8 block quant error
    # error feedback accumulates the residual
    grads = {"w": g}
    red, err = compression.compressed_allreduce(grads, axis_name=None
                                                ) if False else (None, None)
    # (psum needs a mapped axis; unit-test the residual math directly)
    q2, s2 = compression.compress_int8(g)
    resid = g - compression.decompress_int8(q2, s2, g.shape, jnp.float32)
    assert float(jnp.abs(resid).max()) <= float(s2.max()) * 0.5 + 1e-6


def test_compressed_allreduce_under_shard_map():
    n = len(jax.devices())
    mesh = jax.make_mesh((n,), ("d",))
    from jax.sharding import PartitionSpec as P
    g = {"w": jnp.ones((n, 64), jnp.float32)}

    def f(gs):
        red, err = compression.compressed_allreduce(gs, "d")
        return red, err
    out, err = jax.shard_map(f, mesh=mesh, in_specs=(P("d"),),
                             out_specs=P("d"))(g)
    # sum over n shards of ones = n (per row)
    assert np.allclose(np.asarray(out["w"]), n, atol=0.1)


def test_checkpoint_atomic_and_prunes():
    with tempfile.TemporaryDirectory() as d:
        tree = {"a": jnp.arange(6).reshape(2, 3),
                "b": {"c": jnp.ones(3, jnp.bfloat16)}}
        for step in (1, 2, 3, 4):
            ckpt_lib.save(d, step, tree, keep_last=2)
        assert ckpt_lib.latest_step(d) == 4
        assert sorted(ckpt_lib.latest_steps(d)) == [3, 4]
        back = ckpt_lib.restore(d, 4, tree)
        assert np.array_equal(np.asarray(back["a"]), np.asarray(tree["a"]))
        assert back["b"]["c"].dtype == jnp.bfloat16
        # a stale .tmp dir is never listed as a checkpoint
        os.makedirs(os.path.join(d, "step_9.tmp"))
        assert ckpt_lib.latest_step(d) == 4


def test_checkpoint_elastic_reshard():
    """Restore applies NEW shardings to the stored (unsharded) arrays —
    the elastic-rescale path (512-chip save -> 256-chip restore)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    with tempfile.TemporaryDirectory() as d:
        tree = {"w": jnp.arange(32, dtype=jnp.float32).reshape(4, 8)}
        ckpt_lib.save(d, 1, tree)
        n = len(jax.devices())
        mesh = jax.make_mesh((n,), ("data",))
        sh = {"w": NamedSharding(mesh, P("data"))} if 4 % n == 0 else \
            {"w": NamedSharding(mesh, P())}
        back = ckpt_lib.restore(d, 1, tree, shardings=sh)
        assert np.array_equal(np.asarray(back["w"]), np.asarray(tree["w"]))
        assert back["w"].sharding == sh["w"]


def test_trainer_resume_and_preemption():
    m = build("chatglm3-6b", reduced=True)
    dcfg = DataConfig(vocab_size=m.cfg.vocab_size, seq_len=16,
                      global_batch=2)
    with tempfile.TemporaryDirectory() as d:
        tcfg = TrainerConfig(ckpt_dir=d, ckpt_every=4, log_every=2)
        ocfg = adamw.AdamWConfig(total_steps=20, warmup_steps=2)
        tr = Trainer(m, dcfg, ocfg, tcfg)
        out = tr.run(m.init(jax.random.PRNGKey(0)), num_steps=6)
        assert out["step"] == 6
        # simulated preemption: handler sets the flag mid-run
        tr2 = Trainer(m, dcfg, ocfg, tcfg)
        tr2._preempted = True
        out2 = tr2.run(m.init(jax.random.PRNGKey(1)), num_steps=12)
        assert out2["preempted"] and out2["step"] == 6  # saved, no steps
        # a fresh trainer resumes from 6 and continues
        tr3 = Trainer(m, dcfg, ocfg, tcfg)
        out3 = tr3.run(m.init(jax.random.PRNGKey(2)), num_steps=10)
        assert out3["step"] == 10


def test_server_generate_and_collect():
    from repro.runtime.server import Server, ServerConfig
    m = build("chatglm3-6b", reduced=True)
    params = m.init(jax.random.PRNGKey(0))
    srv = Server(m, ServerConfig(batch=2, max_len=32, block_tokens=4,
                                 collect_every=6))
    prompts = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
    out = srv.generate(params, prompts, max_new=10)
    assert out.shape == (2, 10)
    assert len(srv.reports) >= 1
    assert srv.kv_rss_bytes() > 0
