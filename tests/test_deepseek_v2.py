"""DeepSeek-V2 on the paged serving path against the float32 reference
(`bench/models/deepseek_v2_reference.py`, which imports nothing of the
program), at a smoke size on the CPU: one dense layer then two MoE
layers, latent attention with rope dims 8 under YaRN, a router over 8
experts of which this share holds 2, 2 shared experts, top-6.

The program runs in float32 here, so the absorbed attention, the latent
K=V slots, the rope column permutation and the held-expert layer must
give the reference's logits up to float32 rounding (1e-4 of the logits'
spread; a missing absorption, rope or expert term moves them by O(1)).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.models import deepseek_v2 as fam
from bench.models import deepseek_v2_reference as ref
from repro.configs.base import YarnScaling
from repro.models import mla
from repro.models import moe as moe_lib
from repro.models.model import Model
from repro.runtime.server import Request, Server, ServerConfig

SMOKE = ref.Shapes(
    layers=3, dense_layers=1, hidden=64, heads=4, kv_lora=32, nope=16,
    rope=8, v_head=16, ffn=96, moe_ffn=32, experts=8, held_first=2, held=2,
    shared=2, top_k=6, norm_topk=False, routed_scale=1.0, vocab=128,
    eps=1e-6, theta=10000.0, yarn=(40.0, 4096, 32.0, 1.0, 0.707, 0.707),
    dtype="float32")
KEY = ref.seed_key(2 ** 33 + 5)
TOL = 1e-4          # in standard deviations of a position's logits


def _server(s, batch=2, max_len=64):
    return Server(Model(fam.model_config("deepseek-v2-smoke", s)),
                  ServerConfig(batch=batch, max_len=max_len, block_tokens=4,
                               window=8, collect_every=8, eos_token=-1))


@pytest.mark.parametrize("norm_topk", [False, True])
def test_paged_decode_matches_reference(norm_topk):
    """Prompts teacher-forced through the pool, then decoding on the
    program's own tokens: every position's logits against the
    reference's full forward over the same tokens."""
    s = dataclasses.replace(SMOKE, norm_topk=norm_topk)
    params = fam.program_params(s, KEY)
    w = ref.weights(s, KEY)
    srv = _server(s)
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, s.vocab, (2, 13)).astype(np.int32)
    forced = np.concatenate([prompt, np.full((2, 11), -1, np.int32)], 1)
    logits, toks, _ = srv.decode_window(params, jnp.asarray(forced))
    logits, toks = np.asarray(logits), np.asarray(toks)
    for b in range(2):
        # what each step fed: the prompt, then the tokens sampled
        fed = np.concatenate([prompt[b], toks[b, 12:-1]])
        want = np.asarray(ref.logits(s, w, jnp.asarray(fed)))
        gap = np.abs(logits[b] - want).max(-1) / want.std(-1)
        assert gap.max() < TOL, (b, gap.max())
    # the MoE layers counted their picks
    steps, n_moe = forced.shape[1], s.layers - s.dense_layers
    assert 0 < int(srv.state["expert_picks"]) <= steps * n_moe * 2 * s.top_k


def test_serve_logs_expert_counters_per_window():
    s = SMOKE
    params = fam.program_params(s, KEY)
    srv = _server(s, batch=3)
    reqs = [Request(prompt=list(range(1, 2 + i)), max_new=5 + 3 * i)
            for i in range(5)]
    srv.serve(params, reqs)
    n_moe = s.layers - s.dense_layers
    for e in srv.serve_log:
        assert e["experts_read"] == srv.cfg.window * n_moe * s.held
        # picks land only on active lanes: at most active x k a layer-step
        assert 0 <= e["expert_picks"] <= \
            e["active"] * s.top_k * srv.cfg.window * n_moe
    assert sum(e["expert_picks"] for e in srv.serve_log) == \
        int(srv.state["expert_picks"])
    assert srv.serve_log[-1]["expert_picks"] == 0     # the drain window


def _program_layer(s, first, count, x, with_shared):
    """The program's MoE layer 0 of a share holding [first, first+count)."""
    share = dataclasses.replace(s, held_first=first, held=count)
    p = jax.tree.map(lambda a: a[0],
                     fam.program_params(share, KEY)["layers"]["moe"])
    if not with_shared:
        p = {k: v for k, v in p.items() if k != "shared"}
    cfg = fam.model_config("share", share)
    out, picks = moe_lib.held_experts_block(p, x[:, None], cfg)
    return np.asarray(out[:, 0]), np.asarray(picks)


def test_shares_sum_to_the_uncut_layer():
    """Over the 4 shards of a layer, the routed parts plus the shared
    experts counted once give the reference's layer holding all 8."""
    s = SMOKE
    x = jnp.asarray(np.random.default_rng(2).normal(size=(16, s.hidden)),
                    jnp.float32)
    # the first share's output carries the shared experts, the others not
    total = sum(_program_layer(s, f, 2, x, f == 0)[0] for f in range(0, 8, 2))
    whole = dataclasses.replace(s, held_first=0, held=8)
    lw = jax.tree.map(lambda a: a[0], ref.weights(whole, KEY)["moe"])
    want = np.asarray(ref.routed(whole, lw, x) + ref.shared(whole, lw, x))
    assert np.abs(total - want).max() < 1e-5 * np.abs(want).max()
    # and every pick lands in exactly one share
    picks = sum(_program_layer(s, f, 2, x, False)[1].sum()
                for f in range(0, 8, 2))
    assert picks == 16 * s.top_k


def test_no_token_is_dropped():
    """Every row routes to the same expert: each still gets that expert's
    whole contribution (a capacity dispatch would drop most of them)."""
    s = dataclasses.replace(SMOKE, held_first=0, held=8, top_k=2,
                            norm_topk=True)
    cfg = fam.model_config("all", s)
    p = jax.tree.map(lambda a: a[0], fam.program_params(s, KEY)["layers"]["moe"])
    p = {k: v for k, v in p.items() if k != "shared"}
    # a router that scores expert 5 far above the rest for every row
    p["router"] = jnp.zeros_like(p["router"]).at[0, 5].set(50.0) \
        .at[0, 3].set(40.0)
    t = 64
    x = jnp.asarray(np.abs(np.random.default_rng(3).normal(
        size=(t, s.hidden))) + 0.5, jnp.float32)
    out, picks = moe_lib.held_experts_block(p, x[:, None], cfg)
    assert moe_lib.capacity(t, cfg) < t            # the old dispatch's drop
    assert list(np.asarray(picks)) == [0, 0, 0, t, 0, t, 0, 0]

    def expert(e, xe):
        h = jax.nn.silu(xe @ p["wg"][e]) * (xe @ p["wi"][e])
        return h @ p["wo"][e]
    scores = jax.nn.softmax(x @ p["router"], -1)
    w5 = scores[:, 5] / (scores[:, 5] + scores[:, 3])
    want = w5[:, None] * expert(5, x) + (1 - w5)[:, None] * expert(3, x)
    np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("norm_topk", [False, True])
def test_gates_follow_norm_topk_prob(norm_topk):
    s = dataclasses.replace(SMOKE, norm_topk=norm_topk, routed_scale=2.5,
                            held_first=0, held=8)
    x = jnp.asarray(np.random.default_rng(4).normal(size=(8, s.hidden)),
                    jnp.float32)
    lw = jax.tree.map(lambda a: a[0], ref.weights(s, KEY)["moe"])
    g = np.asarray(ref.gates(s, lw, x))
    scores = np.asarray(jax.nn.softmax(x @ lw["router"], -1))
    top = np.sort(scores, -1)[:, -s.top_k:].sum(-1)
    want = np.ones(8) if norm_topk else 2.5 * top
    np.testing.assert_allclose(g.sum(-1), want, rtol=1e-5)
    got = _program_layer(s, 0, 8, x, False)[0]
    want_out = np.asarray(ref.routed(s, lw, x))
    assert np.abs(got - want_out).max() < 1e-5 * np.abs(want_out).max()


def test_yarn_by_hand():
    """DeepSeek-V2-Lite's rope: 64 dims, base 10000, factor 40 over 4096
    positions, beta 32 / 1, mscale = mscale_all_dim = 0.707."""
    y = YarnScaling(factor=40, original_max_position_embeddings=4096,
                    beta_fast=32, beta_slow=1, mscale=0.707,
                    mscale_all_dim=0.707)
    # 64 ln(4096 / (32 * 2 pi)) / (2 ln 10000) = 10.47 -> 10;
    # 64 ln(4096 / (2 pi)) / (2 ln 10000) = 22.51 -> 23
    assert mla.yarn_find_correction_range(32, 1, 64, 10000, 4096) == (10, 23)
    m = mla.yarn_get_mscale(40, 0.707)
    assert m == pytest.approx(1 + 0.0707 * 3.6888794541, rel=1e-9)
    assert m == pytest.approx(1.26080, abs=1e-5)
    inv, factor = mla.rope_table(64, 10000.0, y)
    assert factor == 1.0                       # mscale / mscale_all_dim
    extra = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    # below the range: unscaled; above: divided by the factor; between: a
    # linear blend, (i - 10) / 13 of the way to the scaled one
    assert inv[:10] == pytest.approx(extra[:10], rel=1e-6)
    assert inv[23:] == pytest.approx(extra[23:] / 40, rel=1e-6)
    r = (15 - 10) / 13
    assert inv[15] == pytest.approx(extra[15] * (1 - r) + extra[15] / 40 * r,
                                    rel=1e-6)
    cfg = fam.model_config("lite", dataclasses.replace(
        SMOKE, nope=128, rope=64, yarn=(40.0, 4096, 32.0, 1.0, 0.707, 0.707)))
    assert mla.softmax_scale(cfg) == pytest.approx(
        192 ** -0.5 * 1.2608040 ** 2, rel=1e-6)
    # the reference computes the same table on its own
    s = dataclasses.replace(SMOKE, rope=64)
    ref_inv, ref_m = ref.rotary(s)
    np.testing.assert_allclose(ref_inv, inv, rtol=1e-6)
    assert ref_m == 1.0
    assert ref.softmax_scale(dataclasses.replace(s, nope=128)) == \
        pytest.approx(mla.softmax_scale(cfg), rel=1e-12)
