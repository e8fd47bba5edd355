"""Continuous batching through the fused serving windows: lane
lifecycle (admit -> decode -> finish -> free -> refill), in-scan
sampling, and the pool invariants under lane churn.

The load-bearing contracts:
  * `Server.serve` sustains churn at exactly ONE dispatch per window,
    with lane events (free finished lanes' KV through the pool op
    stream, admit from the queue) resolved at window boundaries INSIDE
    the window dispatch (`engine.window_program`'s pre_fn plumbing);
  * a finished lane's freed slots return to the free rings with the
    carried allocator state consistent (`check_freelist`), and a
    refilled lane decodes bit-identically to a fresh server on the same
    prompt;
  * `generate`'s sampling params are live: greedy stays bit-identical
    to the pre-sampler path, `greedy=False` without a key refuses
    instead of silently decoding greedily."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import kvcache as kvc
from repro.models.model import build
from repro.runtime.server import Completion, Request, Server, ServerConfig
from test_pool_collector import check_freelist

B, W = 2, 4
KW = dict(batch=B, max_len=32, block_tokens=4, collect_every=W, window=W)

_MODELS = {}


def _model(arch="chatglm3-6b"):
    if arch not in _MODELS:
        m = build(arch, reduced=True)
        _MODELS[arch] = (m, m.init(jax.random.PRNGKey(0)))
    return _MODELS[arch]


def _prompt(n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 256, (n,)).tolist()


# ---------------------------------------------------------------------------
# lane free/refill pool invariants
# ---------------------------------------------------------------------------
def test_free_lanes_returns_slots_to_rings():
    """After a lane finishes, every slot it owned returns to the free
    rings: counts restored, sb_occ/slot_ref consistent (the carried
    allocator state never drifts — check_freelist oracle)."""
    m, params = _model()
    srv = Server(m, ServerConfig(**KW))
    pcfg = srv.kv_cfg.pool_config()
    free0 = int(jnp.sum(srv.state["pool"]["free_count"]))

    prompts = jnp.asarray(np.random.default_rng(3).integers(
        0, m.cfg.vocab_size, (B, 3)), jnp.int32)
    srv.generate(params, prompts, max_new=6)
    used = int(jnp.sum(srv.state["block_tables"] >= 0))
    assert used > 0
    assert int(jnp.sum(srv.state["pool"]["free_count"])) == free0 - used
    check_freelist(srv.state["pool"], cfg=pcfg)

    # finish lane 0 through the op stream; lane 1 keeps its KV
    lane0 = jnp.asarray([True, False])
    state = jax.jit(lambda s: kvc.free_lanes(srv.kv_cfg, s, lane0))(
        srv.state)
    freed = used - int(jnp.sum(state["block_tables"] >= 0))
    assert freed > 0
    assert int(jnp.sum(state["pool"]["free_count"])) == \
        free0 - used + freed, "freed slots did not return to the rings"
    check_freelist(state["pool"], cfg=pcfg)
    assert not bool(state["active"][0]) and bool(state["active"][1])
    assert int(state["pos"][0]) == 0 and int(state["pos"][1]) > 0

    # free is idempotent at the op level: dead ids drop
    state2 = jax.jit(lambda s: kvc.free_lanes(srv.kv_cfg, s, lane0))(state)
    assert int(jnp.sum(state2["pool"]["free_count"])) == \
        int(jnp.sum(state["pool"]["free_count"]))
    check_freelist(state2["pool"], cfg=pcfg)

    # the freed (inactive) lane's attend returns ZEROS — not a masked
    # softmax degenerating to a neighbor lane's payload mean
    q = jnp.ones((B, m.cfg.num_heads, m.cfg.resolved_head_dim),
                 jnp.float32)
    out, _ = kvc.attend(srv.kv_cfg, state2, 0, q)
    assert bool(jnp.all(out[0] == 0)), "inactive lane leaked KV data"
    assert bool(jnp.any(out[1] != 0))


def test_serve_one_dispatch_per_window_and_drains_pool():
    """Lane churn (more requests than lanes) at exactly 1 dispatch per
    window; the drain window frees the last lanes' KV through the op
    stream, so the pool ends empty and the allocator state consistent."""
    m, params = _model()
    srv = Server(m, ServerConfig(**KW))
    reqs = [Request(prompt=_prompt(3, 1), max_new=5),
            Request(prompt=_prompt(2, 2), max_new=9),
            Request(prompt=_prompt(4, 3), max_new=3)]
    results = srv.serve(params, reqs)
    assert srv.dispatches == len(srv.serve_log) > 0
    assert all(isinstance(r, Completion) for r in results)
    assert [len(r.tokens) for r in results] == [5, 9, 3]
    # the third request could only run on a refilled lane
    assert results[2].windows[0] > 0
    # drained: no live objects, all slots back on the rings, RSS zero;
    # the server hands back the fixed-batch contract (lanes active,
    # clocks reset) for later generate/decode_step use
    assert int(jnp.sum(srv.state["block_tables"] >= 0)) == 0
    assert bool(jnp.all(srv.state["active"]))
    assert int(jnp.sum(srv.state["pos"])) == 0
    assert srv.kv_rss_bytes() == 0.0
    check_freelist(srv.state["pool"], cfg=srv.kv_cfg.pool_config())
    # RSS tracked the churn down: peak > final
    rss = [e["rss_bytes"] for e in srv.serve_log]
    assert max(rss) > rss[-1] == 0.0


@pytest.mark.parametrize("use_pallas", [False, True])
def test_refilled_lane_bit_identical_to_fresh_server(use_pallas):
    """A refilled lane (slots reused from a freed predecessor, pool
    shared with a live neighbor) decodes bit-identically to a fresh
    server decoding the same prompt — migration-transparent pointer
    dereferences + per-lane pos make lane history invisible — under
    the jnp collector and the Pallas one."""
    m, params = _model()
    prompt_c = _prompt(3, 7)
    # lane churn: rid0 finishes fast -> its lane refills with rid2
    reqs = [Request(prompt=_prompt(2, 5), max_new=2),
            Request(prompt=_prompt(4, 6), max_new=14),
            Request(prompt=prompt_c, max_new=6)]
    srv = Server(m, ServerConfig(use_pallas=use_pallas, **KW))
    res = srv.serve(params, reqs)
    assert res[2].windows[0] > 0, "rid2 was not a refill"

    fresh = Server(m, ServerConfig(use_pallas=use_pallas, **KW))
    ref = fresh.serve(params, [Request(prompt=prompt_c, max_new=6)])
    assert res[2].tokens == ref[0].tokens
    assert res[2].finish_reason == ref[0].finish_reason


def test_serve_eos_finishes_lane():
    """A sampled EOS retires the request at the window boundary with
    finish_reason 'eos' (the EOS token itself is the last output)."""
    m, params = _model()
    probe = Server(m, ServerConfig(**KW))
    first = int(probe.serve(params,
                            [Request(prompt=_prompt(3, 9),
                                     max_new=1)])[0].tokens[0])
    srv = Server(m, ServerConfig(eos_token=first, **KW))
    res = srv.serve(params, [Request(prompt=_prompt(3, 9), max_new=8)])
    assert res[0].finish_reason == "eos"
    assert res[0].tokens[-1] == first
    assert len(res[0].tokens) < 8


def test_serve_caps_at_lane_capacity():
    """A request whose prompt+output would overrun max_len finishes
    with 'length' at the capacity instead of decoding dropped tokens."""
    m, params = _model()
    cap = 8
    srv = Server(m, ServerConfig(batch=B, max_len=cap, block_tokens=4,
                                 collect_every=W, window=W))
    res = srv.serve(params, [Request(prompt=_prompt(3, 4), max_new=50)])
    assert res[0].finish_reason == "length"
    assert len(res[0].tokens) == cap - 3 + 1  # steps 2..7 emit outputs


# ---------------------------------------------------------------------------
# in-scan sampling
# ---------------------------------------------------------------------------
def test_generate_nongreedy_requires_key():
    """greedy=False without a key must refuse — it used to silently
    decode greedily (the dead-parameter bug)."""
    m, params = _model()
    srv = Server(m, ServerConfig(**KW))
    prompts = jnp.zeros((B, 2), jnp.int32)
    with pytest.raises(ValueError, match="PRNG"):
        srv.generate(params, prompts, max_new=2, greedy=False)


def test_generate_sampled_reproducible_and_distinct():
    """Sampling runs in-scan off the carried key: same key -> identical
    stream, different key -> different stream; greedy output is
    unaffected by the sampler riding the carry."""
    m, params = _model()
    prompts = jnp.asarray(np.random.default_rng(11).integers(
        0, m.cfg.vocab_size, (B, 3)), jnp.int32)
    cfg = ServerConfig(temperature=1.5, top_k=8, **KW)
    srv = Server(m, cfg)
    out_greedy = srv.generate(params, prompts, max_new=8)
    srv.reset()
    s1 = srv.generate(params, prompts, max_new=8, greedy=False,
                      key=jax.random.PRNGKey(1))
    srv.reset()
    s2 = srv.generate(params, prompts, max_new=8, greedy=False,
                      key=jax.random.PRNGKey(1))
    srv.reset()
    s3 = srv.generate(params, prompts, max_new=8, greedy=False,
                      key=jax.random.PRNGKey(2))
    assert np.array_equal(np.asarray(s1), np.asarray(s2))
    assert not np.array_equal(np.asarray(s1), np.asarray(s3))
    assert s1.shape == out_greedy.shape
    assert bool(jnp.all((s1 >= 0) & (s1 < m.cfg.vocab_size)))
    # greedy on the same server object still matches a fresh greedy run
    srv.reset()
    again = srv.generate(params, prompts, max_new=8)
    assert np.array_equal(np.asarray(again), np.asarray(out_greedy))


def test_sampled_lanes_top_k_support():
    """top_k restricts every sampled token to that step's k best logits
    (checked against the per-step teacher-forced logits)."""
    m, params = _model()
    prompts = jnp.asarray(np.random.default_rng(13).integers(
        0, m.cfg.vocab_size, (B, 2)), jnp.int32)
    k = 4
    srv = Server(m, ServerConfig(temperature=2.0, top_k=k, **KW))
    out = srv.generate(params, prompts, max_new=6, greedy=False,
                       key=jax.random.PRNGKey(5))
    # replay the sampled stream teacher-forced through a fresh server to
    # recover each step's logits, then check membership in its top-k
    replay = Server(m, ServerConfig(**KW))
    # (the last sampled token, fed too, fills the second window)
    forced = jnp.concatenate([prompts, out], axis=1)
    logits, _, _ = replay.decode_window(params, forced)
    steps = logits[:, prompts.shape[1] - 1:-1]          # [B, 6, V]
    topk_ids = jnp.argsort(steps, axis=-1)[..., -k:]
    for b in range(B):
        for t in range(out.shape[1]):
            assert int(out[b, t]) in np.asarray(topk_ids[b, t]), \
                f"lane {b} step {t}: sampled outside top-{k}"


def test_serve_rejects_oversized_or_empty_prompts():
    """Prompts that cannot fit a lane refuse at submission — KV appends
    past capacity silently drop, so decoding them would condition on a
    truncated prompt."""
    m, params = _model()
    srv = Server(m, ServerConfig(**KW))
    with pytest.raises(ValueError, match="prompt length"):
        srv.serve(params, [Request(prompt=_prompt(KW["max_len"], 1),
                                   max_new=2)])
    with pytest.raises(ValueError, match="prompt length"):
        srv.serve(params, [Request(prompt=[], max_new=2)])
    with pytest.raises(ValueError, match="max_new"):
        srv.serve(params, [Request(prompt=[1, 2], max_new=0)])


def test_generate_after_serve_reuses_the_server():
    """serve hands the server back in the fixed-batch contract: a
    subsequent generate decodes on live lanes (bit-identical to a fresh
    server), not on the drained serve masks."""
    m, params = _model()
    prompts = jnp.asarray(np.random.default_rng(17).integers(
        0, m.cfg.vocab_size, (B, 3)), jnp.int32)
    srv = Server(m, ServerConfig(**KW))
    srv.serve(params, [Request(prompt=_prompt(3, 15), max_new=4)])
    out = srv.generate(params, prompts, max_new=5)
    fresh = Server(m, ServerConfig(**KW))
    ref = fresh.generate(params, prompts, max_new=5)
    assert np.array_equal(np.asarray(out), np.asarray(ref))
