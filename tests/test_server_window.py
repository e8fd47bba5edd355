"""Scanned decode windows: `Server.decode_window` (the serving window
`_win_serve` with no lane events: W model steps + the window-closing
collect+backend as ONE jitted scan) must be BIT-identical to W sequential
`Server.decode_step`s, the per-step reference — logits, pool bytes
(scratch row included), block tables, backend state and collect reports —
for both collector paths (jnp oracle and Pallas interpret) and a
stateless and a stateful backend. Plus the window's alignment contract,
the clock across calls, and the `generate` e2e ride."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine as eng
from repro.models.model import build
from repro.runtime.server import Server, ServerConfig

B, EVERY = 2, 4
KW = dict(batch=B, max_len=32, block_tokens=4, collect_every=EVERY)

_MODELS = {}


def _model(arch="chatglm3-6b"):
    if arch not in _MODELS:
        m = build(arch, reduced=True)
        _MODELS[arch] = (m, m.init(jax.random.PRNGKey(0)))
    return _MODELS[arch]


def _toks(m, t, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, m.cfg.vocab_size, (B, t)),
                       jnp.int32)


def _assert_state_equal(a, b):
    flat_a, _ = jax.tree_util.tree_flatten_with_path(a)
    flat_b = jax.tree_util.tree_leaves(b)
    for (path, x), y in zip(flat_a, flat_b):
        assert np.array_equal(np.asarray(x), np.asarray(y)), \
            f"kv state diverged at {jax.tree_util.keystr(path)}"


@pytest.mark.parametrize("use_pallas", [False, True])
@pytest.mark.parametrize("backend", ["proactive", "mglru"])
@pytest.mark.parametrize("arch,t", [("chatglm3-6b", 2 * EVERY),
                                    ("olmoe-1b-7b", EVERY)])
def test_decode_window_matches_per_step(use_pallas, backend, arch, t):
    """One window dispatch == t per-step dispatches, bit for bit: logits,
    sampled tokens, pool state (data incl. the scratch row, table, tiers,
    counters), the backend's carried state (mglru's generations), and
    reports. t covers one and two collects a window; the MoE arch covers
    the expert path inside the layer scan."""
    m, params = _model(arch)
    toks = _toks(m, t)
    cfg = ServerConfig(use_pallas=use_pallas, backend=backend, **KW)
    srv_a, srv_b = Server(m, cfg), Server(m, cfg)

    logits_a = jnp.stack(
        [srv_a.decode_step(params, toks[:, i])[0] for i in range(t)],
        axis=1)
    logits_b, sampled_b, rep = srv_b.decode_window(params, toks)

    assert np.array_equal(np.asarray(logits_a), np.asarray(logits_b))
    assert np.array_equal(np.asarray(jnp.argmax(logits_a, -1)),
                          np.asarray(sampled_b))
    _assert_state_equal(srv_a.state, srv_b.state)
    assert srv_a._steps == srv_b._steps
    # reports at window closers match the per-step path's float dicts
    reps_b = eng.window_reports(rep)
    assert len(reps_b) == t // EVERY
    assert srv_a.reports == reps_b
    # the whole window was ONE dispatch vs t
    assert (srv_a.dispatches, srv_b.dispatches) == (t, 1)


def test_decode_window_resumes_clock_across_calls():
    """Successive windows share the op clock: two one-window calls close
    one collect each and end where one two-window call ends."""
    m, params = _model()
    toks = _toks(m, 2 * EVERY)
    srv_a, srv_b = Server(m, ServerConfig(**KW)), Server(m, ServerConfig(**KW))
    la1, _, r1 = srv_a.decode_window(params, toks[:, :EVERY])
    la2, _, r2 = srv_a.decode_window(params, toks[:, EVERY:])
    lb, _, rb = srv_b.decode_window(params, toks)
    assert len(eng.window_reports(r1)) == len(eng.window_reports(r2)) == 1
    assert eng.window_reports(r1) + eng.window_reports(r2) == \
        eng.window_reports(rb)
    assert np.array_equal(np.asarray(jnp.concatenate([la1, la2], axis=1)),
                          np.asarray(lb))
    _assert_state_equal(srv_a.state, srv_b.state)
    assert srv_a._steps == srv_b._steps == 2 * EVERY


def test_decode_window_rejects_unaligned_windows():
    """The window runs whole collect windows from a collect boundary: a
    length that is not a multiple of collect_every, and a clock that
    `decode_step` left between boundaries, each raise."""
    m, params = _model()
    toks = _toks(m, EVERY + 2)
    srv = Server(m, ServerConfig(**KW))
    with pytest.raises(ValueError, match=f"T={EVERY + 2}.*"
                       f"collect_every={EVERY}"):
        srv.decode_window(params, toks)
    assert (srv._steps, srv.dispatches) == (0, 0)
    srv.decode_step(params, toks[:, 0])
    with pytest.raises(ValueError, match=r"clock \(1 steps\)"):
        srv.decode_window(params, toks[:, 1:1 + EVERY])
    assert (srv._steps, srv.dispatches) == (1, 1)


@pytest.mark.parametrize("max_new", [10, 8],
                         ids=["whole-windows", "ragged-tail"])
def test_generate_rides_windows(max_new):
    """`generate` == the manual per-step greedy loop, at ceil(steps / W)
    dispatches. 3 + 10 - 1 = 12 steps are whole windows of 4 and close
    the per-step loop's 3 collects; 3 + 8 - 1 = 10 steps pad the last
    window with self-feed steps, whose tokens are dropped."""
    m, params = _model()
    prompts = _toks(m, 3, seed=1)

    srv_w = Server(m, ServerConfig(**KW))
    out_w = srv_w.generate(params, prompts, max_new=max_new)

    srv_s = Server(m, ServerConfig(**KW))
    tok = None
    outs = []
    for t in range(prompts.shape[1]):
        logits, _ = srv_s.decode_step(params, prompts[:, t])
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    outs.append(tok)
    for _ in range(max_new - 1):
        logits, _ = srv_s.decode_step(params, tok)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        outs.append(tok)
    out_s = jnp.stack(outs, axis=1)

    assert out_w.shape == (B, max_new)
    assert np.array_equal(np.asarray(out_w), np.asarray(out_s))
    total = prompts.shape[1] + max_new - 1
    if total % EVERY == 0:
        assert srv_w.reports == srv_s.reports
    assert srv_w.dispatches == -(-total // EVERY)
    assert srv_w._steps == srv_w.dispatches * EVERY
    assert srv_s.dispatches == total


def test_paged_decode_matches_dense_decode():
    """The fixed single-phase server transition must reproduce the dense
    (ring-cache) decode path: each layer's k/v derives from the previous
    layer's output, and the appended token attends to itself — the seed's
    two-phase loop failed both."""
    m, params = _model()
    t = 6
    toks = _toks(m, t, seed=2)
    srv = Server(m, ServerConfig(**KW))
    dense_state = m.init_decode_state(B, t)
    for i in range(t):
        paged, _ = srv.decode_step(params, toks[:, i])
        dense, dense_state = m.decode_step(params, dense_state, toks[:, i])
        gap = float(jnp.abs(paged - dense).max())
        assert gap < 0.05, f"step {i}: paged/dense divergence {gap}"


def test_decode_past_max_len_drops_instead_of_corrupting():
    """Tokens past the pool's block capacity are DROPPED: an unguarded
    append would clamp the object id into the table and overwrite a LIVE
    block's bytes (another sequence's KV). Decoding past max_len must
    leave every in-capacity byte of the pool untouched."""
    m, params = _model()
    cap = 8                                   # 2 blocks of 4 per lane
    srv = Server(m, ServerConfig(batch=B, max_len=cap, block_tokens=4,
                                 collect_every=64))
    toks = _toks(m, cap + 3, seed=3)
    for i in range(cap):
        srv.decode_step(params, toks[:, i])
    data_at_cap = np.asarray(srv.state["pool"]["data"]).copy()
    for i in range(cap, cap + 3):
        logits, _ = srv.decode_step(params, toks[:, i])
    assert bool(jnp.all(jnp.isfinite(logits)))
    assert np.array_equal(np.asarray(srv.state["pool"]["data"]),
                          data_at_cap), "overflow write corrupted the pool"
    assert int(srv.state["pos"][0]) == cap + 3


def test_generate_max_new_zero():
    """Degenerate request: no crash, empty output, no state change."""
    m, params = _model()
    srv = Server(m, ServerConfig(**KW))
    out = srv.generate(params, _toks(m, 3), max_new=0)
    assert out.shape == (B, 0)
    assert srv._steps == 0


def test_decode_window_seed_token_form():
    """decode_window(params, tok [B], w) == decode_window with an explicit
    [B, w] forced matrix of (seed, -1, ...) — the self-feeding window."""
    m, params = _model()
    seed = _toks(m, 1)[:, 0]
    srv_a, srv_b = Server(m, ServerConfig(**KW)), Server(m, ServerConfig(**KW))
    la, sa, _ = srv_a.decode_window(params, seed, w=EVERY)
    forced = jnp.concatenate(
        [seed[:, None], jnp.full((B, EVERY - 1), -1, jnp.int32)], axis=1)
    lb, sb, _ = srv_b.decode_window(params, forced)
    assert np.array_equal(np.asarray(la), np.asarray(lb))
    assert np.array_equal(np.asarray(sa), np.asarray(sb))
