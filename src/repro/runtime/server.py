"""Batched decode server with the HADES-managed paged KV cache.

The serving hot path runs as SCANNED DECODE WINDOWS: `decode_window`
executes W decode steps — embed, per-layer (qkv -> paged append -> attend
through the object table -> ffn), logits, sample, and the window-closing
collect+MIAD+backend — as ONE jitted `lax.scan`, built on the same
`engine.window_program` machinery (and therefore the same op-clock /
collect-cadence contract) as `Engine.run_window`. `decode_step` is the
per-step reference path: the identical transition, one dispatch per
token, bit-identical to the windowed path (tests/test_server_window.py).

Per layer the residual stream `h` advances BEFORE the next layer's k/v is
derived (each layer's k/v is a function of the previous layers' output —
the old two-phase loop computed every layer's k/v from the embedding and
wrote corrupted bytes into the paged pool).

`overlap_collect=True` is the double-buffered serving loop the ATC/arm
epoch protocol exists for: windows arm one step before closing (objects
dereferenced by an in-flight step carry ATC > 0 and are never migrated),
and `generate` defers each window's report sync until the NEXT window's
dispatch has been issued — collection resolves while decode runs.

CONTINUOUS BATCHING (`Server.serve`, docs/serving.md): lanes carry a
lifecycle — admit -> decode -> finish on EOS/max-tokens -> free -> refill
from the request queue. Lane events resolve at window boundaries and ride
the window dispatch itself (`engine.window_program`'s `pre_fn` plumbing):
finishing a lane frees ALL of its KV objects through the pool op stream
before the window's first step, so churn stays at exactly ONE dispatch
per window while freed cold blocks become the fragmentation the
collector tidies for the backend to reclaim. Sampling (temperature /
top-k, per lane) runs INSIDE the scan under a carried PRNG key
(runtime/sampling.py).

MEASUREMENT (docs/serving.md): `serve` records itself on
`time.perf_counter`'s clock — each window's host phases as profiler spans
(`serve.window`, `serve.<phase>`) and in `serve_log`, each request's
admission, first token and finish on its `Completion` — and the window
program returns the post-window KV gauges, so `serve` runs no device work
of its own for them. The window program's ops carry `jax.named_scope`s
(qkv, kv_append, attention, ffn, record, logits, sample, lane_events,
collect, migrate, backend) that reach the compiled HLO's `op_name` metadata.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import backend as be
from repro.core import collector as col
from repro.core import engine as eng
from repro.core import pool as pl
from repro.models import kvcache as kvc
from repro.models import layers as L
from repro.models import mla
from repro.models import transformer as T
from repro.runtime import sampling


@dataclasses.dataclass
class ServerConfig:
    batch: int = 8
    max_len: int = 256
    block_tokens: int = 16
    collect_every: int = 8
    # tiering backend: any registered name (backend.names()) + its
    # constructor params, built via backend.make at Server construction
    # (typos fail here, not inside a jitted trace)
    backend: str = "proactive"
    backend_params: Optional[Dict] = None
    eos_token: int = 2
    # decode-window length W used by `generate`/`serve` (0 ->
    # collect_every): W steps run as ONE dispatch, window protocol
    # included
    window: int = 0
    # double-buffered serving: windows arm the ATC epoch one step before
    # closing, and `generate`/`serve` sync window N's report only after
    # window N+1's dispatch is in flight
    overlap_collect: bool = False
    # route the collector through the Pallas kernels (interpret on CPU)
    use_pallas: bool = False
    # in-scan sampling defaults for `generate(greedy=False)`:
    # temperature <= 0 is greedy argmax, top_k <= 0 keeps the full vocab
    # (per-request overrides live on `Request`)
    temperature: float = 1.0
    top_k: int = 0


@dataclasses.dataclass
class Request:
    """One generation request for `Server.serve` (continuous batching).
    temperature <= 0 decodes greedily; top_k <= 0 disables the top-k
    filter. Sampled requests (temperature > 0) need `serve(key=...)`."""
    prompt: Sequence[int]
    max_new: int = 32
    temperature: float = 0.0
    top_k: int = 0


@dataclasses.dataclass
class Completion:
    """`Server.serve`'s per-request result. `tokens` are the generated
    tokens (EOS included when it fired); `finish_reason` is "eos" or
    "length" (max_new or lane capacity); `windows` is the [admitted,
    finished] window-index span the request occupied a lane for.

    The stamps are `time.perf_counter()` seconds, the clock of
    `serve_log`: `t_admitted` is the admitting window's `t_dispatch`,
    `t_first_token` the `t_tokens` of the window whose sync delivered the
    first generated token, `t_finished` that of the window that delivered
    the last kept one (window `windows[1] - 1`)."""
    rid: int
    tokens: List[int]
    finish_reason: str
    windows: Tuple[int, int]
    t_admitted: float
    t_first_token: float
    t_finished: float


@dataclasses.dataclass
class _Lane:
    """Host-side lane bookkeeping between window boundaries."""
    rid: int
    req: Request
    admitted_at: int
    steps: int = 0                   # model steps consumed since admit
    out: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    reason: str = ""
    t_admitted: float = 0.0
    t_first_token: float = 0.0
    t_finished: float = 0.0


@contextlib.contextmanager
def _phase(host_ms: Dict[str, float], name: str):
    """One host phase of a serving window: a profiler span
    `serve.<name>` and its milliseconds in `host_ms[name]`. Yields the
    phase's start on `time.perf_counter`."""
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(f"serve.{name}"):
        yield t0
    host_ms[name] = 1e3 * (time.perf_counter() - t0)


def _live_blocks(kvstate: Dict) -> jax.Array:
    """Allocated KV blocks: the block-table entries that hold an id."""
    return jnp.sum(kvstate["block_tables"] >= 0)


class Server:
    """Decode-only server for attention-family models (dense/GQA/MoE)."""

    def __init__(self, model, cfg: ServerConfig):
        assert not model.cfg.block_pattern, \
            "paged serving targets attention archs (SSM decode is O(1))"
        self.model = model
        self.cfg = cfg
        mc = model.cfg
        if mc.kv_lora_rank:     # latent attention: one K=V vector a token
            kv_heads, head_dim, v_dim = \
                1, mc.kv_lora_rank + mc.qk_rope_head_dim, mc.kv_lora_rank
        else:
            kv_heads, head_dim, v_dim = \
                mc.num_kv_heads, mc.resolved_head_dim, 0
        self.kv_cfg = kvc.KVCacheConfig(
            num_layers=mc.num_layers, batch=cfg.batch,
            max_blocks=-(-cfg.max_len // cfg.block_tokens),
            block_tokens=cfg.block_tokens, num_kv_heads=kv_heads,
            head_dim=head_dim, dtype=mc.dtype, v_dim=v_dim)
        self.col_cfg = col.CollectorConfig(use_pallas=cfg.use_pallas)
        self.backend = be.make(cfg.backend, **(cfg.backend_params or {}))
        self.reports: List[Dict] = []
        self.serve_log: List[Dict] = []     # per-window churn/RSS gauges
        self._build_programs()
        self.reset()

    # -- compiled programs -----------------------------------------------------
    def _model_step(self, params, state, tok):
        """The fused decode transition: tok [B] -> (state', logits [B,V]).
        Layers run under lax.scan; each layer derives qkv from the CURRENT
        residual stream (exactly once), appends its k/v to the paged pool
        and attends through the object table. Inactive lanes append
        nothing and attend over zero keys (kvcache's lane mask). The
        scans emit each layer's touched blocks; one `kvc.record` after
        them sets the step's access bits.

        A model with `first_k_dense` leading dense layers runs them first,
        from `params["dense_layers"]`, as a scan of their own at KV layer
        indices [0, k); `params["layers"]` then scan at [k, num_layers).
        A model that holds a share of the experts (`held_experts`) adds
        its MoE layers' top-k picks on held experts of active lanes to
        `state["expert_picks"]` (`reset` seeds it)."""
        mc: ModelConfig = self.model.cfg
        cfg = self.kv_cfg
        x = L.embed(params["embed"], tok)[:, None, :]   # [B,1,D]
        positions = state["pos"][:, None]               # [B,1]
        scale = mla.softmax_scale(mc) if mc.kv_lora_rank else None
        counted = bool(mc.held_experts)

        def layer_body(carry, xs):
            h, st = carry
            li, lp = xs

            def attend(q, k, v):
                st2 = kvc.append_layer(cfg, st, li, k[:, 0],
                                       None if v is None else v[:, 0])
                # pos still points AT the appended token (advance_pos
                # runs after the layer scan) -> the token attends to
                # itself via pos + 1
                out, touched = kvc.attend_read(cfg, st2, li, q[:, 0],
                                               seq_lens=st2["pos"] + 1,
                                               scale=scale)
                return out[:, None], (st2, touched)     # [B,1,H,Dh]

            h, (st, touched), counts = T.decode_layer_step(
                lp, h, mc, positions, attend, lanes=st["active"])
            if counted and "moe" in lp:
                st = dict(st,
                          expert_picks=st["expert_picks"] + jnp.sum(counts))
            return (h, st), touched

        touched = []
        k = mc.first_k_dense
        if k:
            (x, state), t = jax.lax.scan(
                layer_body, (x, state), (jnp.arange(k),
                                         params["dense_layers"]))
            touched.append(t)
        (h, state), t = jax.lax.scan(
            layer_body, (x, state),
            (jnp.arange(k, mc.num_layers), params["layers"]))
        # the step's access bits, once, over the layer-major table
        state = kvc.record(cfg, state, jnp.concatenate(touched + [t]))
        state = kvc.advance_pos(state)
        with jax.named_scope("logits"):
            h = L.rms_norm(h, params["final_ln"], mc.norm_eps)
            out_t = params["embed"].T if mc.tie_embeddings else \
                params["out"]
            logits = L.logits_head(out_t, h)[:, 0]
        return state, logits

    def _build_programs(self):
        every = int(self.cfg.collect_every)
        overlap = bool(self.cfg.overlap_collect)
        cab = functools.partial(kvc.collect_and_backend, self.kv_cfg,
                                self.col_cfg, self.backend)

        def win_step(params, do_sample, carry, forced):
            """One window step: forced token (>= 0) or self-feed the
            previously sampled one (inactive lanes decode a pinned pad
            token; the lane mask drops their pool traffic). With
            `do_sample` (static — a property of the generate/serve
            call) the in-scan sampler picks the next token under the
            carried PRNG key — split once per step, forced steps
            included — with the carried per-lane temperature/top-k
            (temperature <= 0 lanes take argmax); without it the step
            is the bare argmax transition, so the greedy hot path never
            pays the sampler's [B, V] sort + Gumbel draw."""
            tok = jnp.where(forced >= 0, forced, carry["tok"])
            tok = jnp.where(carry["kv"]["active"], tok, 0)
            kvstate, logits = self._model_step(params, carry["kv"], tok)
            with jax.named_scope("sample"):
                if do_sample:
                    key, sub = jax.random.split(carry["key"])
                    nxt = sampling.sample(logits, sub, carry["temp"],
                                          carry["topk"])
                    carry = dict(carry, kv=kvstate, tok=nxt, key=key)
                else:
                    nxt = jnp.argmax(logits, -1).astype(jnp.int32)
                    carry = dict(carry, kv=kvstate, tok=nxt)
            return carry, {"logits": logits, "tok": nxt}

        def win_collect(carry):
            kvstate, report = cab(carry["kv"])
            return dict(carry, kv=kvstate), report

        def win_arm(carry):
            return dict(carry, kv=kvc.arm(carry["kv"]))

        @jax.named_scope("lane_events")
        def win_events(carry, ev):
            """Window-entry lane events, fused into the window dispatch:
            finished lanes free ALL their KV through the pool op stream,
            refilled lanes reset their clock and load their sampling
            params. ev: {"free","admit" [B] bool, "temp" [B] f32,
            "topk" [B] i32}."""
            kv = kvc.free_lanes(self.kv_cfg, carry["kv"], ev["free"])
            kv = kvc.admit_lanes(kv, ev["admit"])
            return dict(carry, kv=kv,
                        temp=jnp.where(ev["admit"], ev["temp"],
                                       carry["temp"]),
                        topk=jnp.where(ev["admit"], ev["topk"],
                                       carry["topk"]))

        def _programs(params, do_sample, pre_fn=None):
            return eng.window_program(
                functools.partial(win_step, params, do_sample),
                win_collect, win_arm,
                every=every, overlap=overlap, pre_fn=pre_fn)

        def aligned(params, carry, toks, do_sample):
            return _programs(params, do_sample)[1](carry, toks)

        def generic(params, carry, toks, step0, do_sample):
            return _programs(params, do_sample)[0](carry, toks, step0)

        def serve_aligned(params, carry, toks, events, do_sample):
            """The continuous-batching window: lane events applied at
            the window entry, then W steps + collect — one dispatch.
            Returns (carry, outs, reports, gauges): `gauges` are the
            post-window KV gauges, computed as `kv_rss_bytes` and
            `kv_live_bytes` compute them (`rss_bytes`, `live_blocks`)."""
            carry, outs, reports = _programs(
                params, do_sample, pre_fn=win_events)[1](carry, toks,
                                                         events)
            kv = carry["kv"]
            gauges = {"rss_bytes": pl.rss_bytes(self.kv_cfg.pool_config(),
                                                kv["pool"]),
                      "live_blocks": _live_blocks(kv)}
            if "expert_picks" in kv:
                gauges["expert_picks"] = kv["expert_picks"]
            return carry, outs, reports, gauges

        def step_apply(params, carry, tok, do_arm, do_collect,
                       do_sample):
            """decode_step's program: the identical transition, collect
            and arm fused in statically (the host knows the clock)."""
            carry, out = win_step(params, do_sample, carry, tok)
            if do_arm:
                carry = win_arm(carry)
            if do_collect:
                carry, report = win_collect(carry)
            else:
                report = eng.zero_report()
            return carry, out, report

        # the decode carry (KV pool + last tokens + sampling key/params)
        # is DONATED: each window updates the paged pool in place instead
        # of double-buffering it per dispatch. params (argnum 0) are NOT
        # donated — they are reused every call. The server never touches
        # a carry after passing it in (all carried leaves are reassigned
        # from the returned carry; tests/test_donation.py). `do_sample`
        # is static: the greedy variant compiles without the sampler.
        self._win_aligned = jax.jit(aligned, donate_argnums=(1,),
                                    static_argnames=("do_sample",))
        self._win_generic = jax.jit(generic, donate_argnums=(1,),
                                    static_argnames=("do_sample",))
        self._win_serve = jax.jit(serve_aligned, donate_argnums=(1,),
                                  static_argnames=("do_sample",))
        self._step_apply = jax.jit(
            step_apply,
            static_argnames=("do_arm", "do_collect", "do_sample"),
            donate_argnums=(1,))

    # -- the decode carry (donated per dispatch, mirrors reassigned) ----------
    def _carry(self) -> Dict:
        return {"kv": self.state, "tok": self._last_tok, "key": self._key,
                "temp": self._temp, "topk": self._topk}

    def _uncarry(self, carry: Dict) -> None:
        self.state, self._last_tok = carry["kv"], carry["tok"]
        self._key = carry["key"]
        self._temp, self._topk = carry["temp"], carry["topk"]

    # -- one decode step across the batch -------------------------------------
    def decode_step(self, params, tokens: jax.Array
                    ) -> Tuple[jax.Array, None]:
        """tokens: [B] -> (logits [B, V], None). ONE dispatch: the model
        step plus — statically, from the host-side window clock — the ATC
        arm and the fused collect+MIAD+backend. The per-step reference
        for `decode_window` (bit-identical transitions)."""
        nxt = self._steps + 1
        every = self.cfg.collect_every
        do_arm = bool(self.cfg.overlap_collect) and \
            nxt % every == every - 1
        do_collect = nxt % every == 0
        carry, out, report = self._step_apply(
            params, self._carry(), jnp.asarray(tokens, jnp.int32),
            do_arm=do_arm, do_collect=do_collect,
            do_sample=self._sample_in_scan)
        self._uncarry(carry)
        self._steps += 1
        self.dispatches += 1
        if do_collect:
            self.reports.append({k: float(v) for k, v in report.items()})
        return out["logits"], None

    # -- scanned decode windows ------------------------------------------------
    def decode_window(self, params, tokens: jax.Array,
                      w: Optional[int] = None):
        """Run a whole decode window as ONE dispatch.

        tokens: [B, T] int32 — entries >= 0 are teacher-forced, entries
        < 0 self-feed the previously sampled token; or [B] (a seed token
        per sequence) with `w` given, running `w` steps (seed then
        self-feed). Every step embeds, runs all layers (paged append +
        attend), computes logits and samples; window-closing steps run
        the fused collect+MIAD+backend in the same program (and, with
        overlap_collect, arm the ATC epoch one step earlier). Uses the
        cond-free window-aligned program when T and the op clock align
        with collect_every, the generic cond-gated one otherwise.

        Returns (logits [B, T, V], sampled [B, T], per-step report
        pytree — feed to engine.window_reports to extract the collects)."""
        toks = jnp.asarray(tokens, jnp.int32)
        if toks.ndim == 1:
            toks = jnp.concatenate(
                [toks[:, None],
                 jnp.full((toks.shape[0], (w or 1) - 1), -1, jnp.int32)],
                axis=1)
        toks = toks.T                                   # scan axis first
        t = int(toks.shape[0])
        every = self.cfg.collect_every
        carry = self._carry()
        if t > 0 and t % every == 0 and self._steps % every == 0:
            carry, outs, reports = self._win_aligned(
                params, carry, toks, do_sample=self._sample_in_scan)
        else:
            carry, outs, reports = self._win_generic(
                params, carry, toks, self._steps,
                do_sample=self._sample_in_scan)
        self._uncarry(carry)
        self._steps += t
        self.dispatches += 1
        return (outs["logits"].transpose(1, 0, 2), outs["tok"].T, reports)

    # -- generate --------------------------------------------------------------
    def generate(self, params, prompts: jax.Array, max_new: int,
                 *, greedy: bool = True, key=None) -> jax.Array:
        """prompts: [B, P], teacher-forced through the same scanned decode
        path (prefill exercises HADES on the prefix blocks), then
        `max_new` tokens — window-by-window (W = cfg.window or
        collect_every), O(tokens / W) dispatches.

        `greedy=True` decodes argmax (bit-identical to the pre-sampler
        path; `key` is optional and only seeds the carried PRNG).
        `greedy=False` samples IN-SCAN with cfg.temperature/cfg.top_k on
        every lane and REQUIRES `key` — sampling without randomness used
        to fall back to greedy silently; now it refuses. (A
        cfg.temperature <= 0 still means argmax — that is lane
        configuration, not a fallback.)

        With overlap_collect the loop is double-buffered: window N's
        report sync (the only host<->device round trip) happens only
        after window N+1's dispatch is in flight, so collection resolves
        while the next window decodes."""
        if not greedy and key is None:
            raise ValueError(
                "generate(greedy=False) samples inside the decode scan "
                "and needs an explicit PRNG `key`")
        b, p = prompts.shape
        if key is not None:
            self._key = jnp.asarray(key)
        self._sample_in_scan = not greedy
        if greedy:
            self._temp = jnp.zeros((b,), jnp.float32)
            self._topk = jnp.zeros((b,), jnp.int32)
        else:
            self._temp = jnp.full((b,), self.cfg.temperature, jnp.float32)
            self._topk = jnp.full((b,), self.cfg.top_k, jnp.int32)
        if max_new <= 0:
            return jnp.zeros((b, 0), jnp.int32)
        total = p + max_new - 1
        forced = jnp.concatenate(
            [jnp.asarray(prompts, jnp.int32),
             jnp.full((b, max_new - 1), -1, jnp.int32)], axis=1)
        w = self.cfg.window or self.cfg.collect_every
        sampled = []
        pending = None
        for lo in range(0, total, w):
            _, toks, rep = self.decode_window(params, forced[:, lo:lo + w])
            sampled.append(toks)
            if self.cfg.overlap_collect:
                if pending is not None:
                    self.reports.extend(eng.window_reports(pending))
                pending = rep
            else:
                self.reports.extend(eng.window_reports(rep))
        if pending is not None:
            self.reports.extend(eng.window_reports(pending))
        out = jnp.concatenate(sampled, axis=1)          # [B, total]
        return out[:, p - 1:]

    # -- continuous batching ---------------------------------------------------
    def serve(self, params, requests: Sequence[Request], *, key=None,
              max_windows: Optional[int] = None) -> List[Completion]:
        """Continuous-batching queue driver (docs/serving.md).

        Rides the fused serving window at exactly ONE dispatch per
        window: each iteration resolves lane events on the host (finish
        -> free, queue -> admit), builds the window's forced-token
        matrix (prompt tokens teacher-forced per lane, -1 self-feeds,
        inactive lanes pinned to 0) and dispatches the event+window
        program — the finished lanes' KV objects are freed through the
        pool op stream INSIDE that dispatch, before the first step. The
        sampled tokens sync back at the window boundary (the host must
        inspect them to schedule lanes — the sync a continuous batcher
        cannot avoid, paid once per W tokens); with overlap_collect the
        collect REPORT sync is still deferred one window.

        A lane finishes on EOS, on its request's max_new, or at the
        lane capacity (cfg.max_len). Prompts must fit a lane
        (0 < len < max_len — longer ones would silently truncate).
        Finished lanes keep decoding until their window ends (overshoot
        tokens are dropped on the host and freed with the lane); the
        final lanes drain through one last all-inactive window so every
        request's KV leaves the pool through the same op stream. Starts
        from a fresh pool (`reset(active=False)`) and ends back in the
        fixed-batch contract (drained pool, all lanes active at pos 0).

        `self.serve_log` gets one entry per window: churn counts, the
        window program's post-window `rss_bytes`/`live_bytes`,
        `lane_steps` (lanes x W) and `useful_lane_steps` (those that fed
        a prompt token or produced a kept token), and on
        `time.perf_counter`'s clock `t_dispatch` (just before the
        dispatch), `t_ready` (the host first holds the window's outputs),
        `t_tokens` (the sampled tokens are on the host) and `host_ms`,
        the milliseconds of each host phase (schedule, inputs, dispatch,
        reports, tokens, lanes, log), each also a profiler span
        `serve.<phase>` inside the step span `serve.window`. A model that
        holds a share of the experts adds the window's `expert_picks`
        (`_model_step`) and `experts_read`, the held experts whose weights
        its MoE layers read: all of them, each layer and step.

        Returns one `Completion` per request, in submission order."""
        w = self.cfg.window or self.cfg.collect_every
        every = self.cfg.collect_every
        if w % every != 0:
            raise ValueError(
                f"serve needs window ({w}) aligned to collect_every "
                f"({every}) — lane events ride the aligned window shape")
        b = self.cfg.batch
        do_sample = any(r.temperature > 0 for r in requests)
        if key is None and do_sample:
            raise ValueError(
                "serve() got sampled requests (temperature > 0) but no "
                "PRNG `key`")
        for rid, r in enumerate(requests):
            if not 0 < len(r.prompt) < self.cfg.max_len:
                raise ValueError(
                    f"request {rid}: prompt length {len(r.prompt)} must "
                    f"be in [1, max_len={self.cfg.max_len}) — longer "
                    "prompts would silently truncate (KV appends past "
                    "lane capacity are dropped)")
            if r.max_new < 1:
                raise ValueError(
                    f"request {rid}: max_new={r.max_new} — a lane "
                    "always emits at least one token")
        self.reset(active=False)
        self._sample_in_scan = do_sample
        if key is not None:
            self._key = jnp.asarray(key)
        queue = collections.deque(enumerate(requests))
        lanes: List[Optional[_Lane]] = [None] * b
        results: List[Optional[Completion]] = [None] * len(requests)
        if max_windows is None:
            # generous safety valve: sequential worst case + drain
            max_windows = 2 + sum(
                -(-(len(r.prompt) + r.max_new) // w) + 1 for r in requests)
        overlap = self.cfg.overlap_collect
        slot_bytes = self.kv_cfg.pool_config().slot_bytes
        mc = self.model.cfg
        # the drop-free expert layer reads every held expert, each MoE
        # layer and step
        experts_read = mc.held_experts[1] * w * (
            mc.num_layers - mc.first_k_dense) if mc.held_experts else None
        picks_seen = 0
        window_idx = 0
        pending = None
        while True:
            host_ms: Dict[str, float] = {}
            with jax.profiler.StepTraceAnnotation("serve.window",
                                                  step_num=window_idx):
                # -- resolve lane events (host side, window boundary) ----
                with _phase(host_ms, "schedule"):
                    free = np.zeros((b,), bool)
                    admit = np.zeros((b,), bool)
                    temp = np.zeros((b,), np.float32)
                    topk = np.zeros((b,), np.int32)
                    for i in range(b):
                        ln = lanes[i]
                        if ln is not None and ln.done:
                            free[i] = True
                            results[ln.rid] = Completion(
                                ln.rid, ln.out, ln.reason,
                                (ln.admitted_at, window_idx),
                                ln.t_admitted, ln.t_first_token,
                                ln.t_finished)
                            lanes[i] = None
                        if lanes[i] is None and queue:
                            rid, req = queue.popleft()
                            lanes[i] = _Lane(rid=rid, req=req,
                                             admitted_at=window_idx)
                            admit[i] = True
                            temp[i] = req.temperature
                            topk[i] = req.top_k
                if not any(lanes) and not free.any():
                    break                 # queue drained, pool empty
                if window_idx >= max_windows:
                    raise RuntimeError(
                        f"serve exceeded max_windows={max_windows} "
                        "(lane scheduling stuck?)")

                # -- the window's forced tokens and lane events ----------
                with _phase(host_ms, "inputs"):
                    toks = np.zeros((b, w), np.int32)
                    for i, ln in enumerate(lanes):
                        if ln is None:
                            continue
                        row = np.full((w,), -1, np.int32)
                        prompt = ln.req.prompt
                        n_force = min(max(len(prompt) - ln.steps, 0), w)
                        row[:n_force] = prompt[ln.steps:ln.steps + n_force]
                        toks[i] = row
                    toks = jnp.asarray(toks.T)
                    events = self._window_events(w, free, admit, temp, topk)

                # -- ONE dispatch: events + W steps + collect ------------
                with _phase(host_ms, "dispatch") as t_dispatch:
                    carry, outs, rep, gauges = self._win_serve(
                        params, self._carry(), toks, events,
                        do_sample=do_sample)
                    self._uncarry(carry)
                self._steps += w
                self.dispatches += 1
                window_idx += 1
                for i in np.flatnonzero(admit):
                    lanes[i].t_admitted = t_dispatch

                # -- window-boundary syncs: reports, then tokens ---------
                # (t_ready: the host first holds this window's outputs —
                # at the report sync, or with overlap_collect, whose
                # report sync reads the previous window, at the tokens)
                with _phase(host_ms, "reports"):
                    if overlap:
                        if pending is not None:
                            self.reports.extend(
                                eng.window_reports(pending))
                        pending = rep
                    else:
                        jax.block_until_ready(rep)
                        t_ready = time.perf_counter()
                        self.reports.extend(eng.window_reports(rep))
                with _phase(host_ms, "tokens"):
                    tok, gauges = jax.device_get((outs["tok"], gauges))
                    t_tokens = time.perf_counter()
                if overlap:
                    t_ready = t_tokens

                # -- schedule lanes off the samples ----------------------
                useful = 0      # lane-steps that fed a prompt or kept token
                with _phase(host_ms, "lanes"):
                    sampled = tok.T                       # [B, w]
                    for i, ln in enumerate(lanes):
                        if ln is None:
                            continue
                        p = len(ln.req.prompt)
                        for t in range(w):
                            if ln.done:
                                break
                            useful += 1
                            s = ln.steps + t
                            if s < p - 1:
                                continue                  # prompt phase
                            ln.out.append(int(sampled[i, t]))
                            if len(ln.out) == 1:
                                ln.t_first_token = t_tokens
                            if ln.out[-1] == self.cfg.eos_token:
                                ln.done, ln.reason = True, "eos"
                            elif len(ln.out) >= ln.req.max_new:
                                ln.done, ln.reason = True, "length"
                            elif s + 1 >= self.cfg.max_len:
                                ln.done, ln.reason = True, "length"
                        if ln.done:
                            ln.t_finished = t_tokens
                        ln.steps += w
                with _phase(host_ms, "log"):
                    self.serve_log.append({
                        "window": window_idx,
                        "active": sum(ln is not None for ln in lanes),
                        "admitted": int(admit.sum()),
                        "freed": int(free.sum()),
                        "queued": len(queue),
                        "rss_bytes": float(gauges["rss_bytes"]),
                        "live_bytes": float(int(gauges["live_blocks"])
                                            * slot_bytes),
                        "lane_steps": b * w,
                        "useful_lane_steps": useful,
                        "t_dispatch": t_dispatch,
                        "t_ready": t_ready,
                        "t_tokens": t_tokens,
                        "host_ms": host_ms,   # "log" lands on exit
                    })
                    if experts_read is not None:
                        picks = int(gauges["expert_picks"])
                        self.serve_log[-1].update(
                            expert_picks=picks - picks_seen,
                            experts_read=experts_read)
                        picks_seen = picks
        if pending is not None:
            self.reports.extend(eng.window_reports(pending))
        assert all(r is not None for r in results)
        # the pool is drained; hand the server back in the fixed-batch
        # contract (all lanes live at pos 0) so a later generate /
        # decode_step does not silently decode on masked lanes
        self.state = dict(self.state,
                          active=jnp.ones((b,), jnp.bool_))
        self._sample_in_scan = False
        return results

    @staticmethod
    def _window_events(w: int, free, admit, temp, topk) -> Dict:
        """The serving window's lane events, [W, B] each: the host's
        window-entry decisions on step 0, nothing on later steps."""
        b = len(free)
        return {
            "free": jnp.zeros((w, b), jnp.bool_).at[0].set(free),
            "admit": jnp.zeros((w, b), jnp.bool_).at[0].set(admit),
            "temp": jnp.zeros((w, b), jnp.float32).at[0].set(temp),
            "topk": jnp.zeros((w, b), jnp.int32).at[0].set(topk),
        }

    def lower_serve_window(self, params, *, do_sample: bool = False):
        """`serve`'s window program (lane events + W steps + collect, the
        one dispatch per window) lowered at this server's geometry, for
        compile checks: its HLO text (`.as_text()`), and `.compile()`'s
        memory analysis. Nothing runs and the carry is not consumed."""
        w = self.cfg.window or self.cfg.collect_every
        b = self.cfg.batch
        no = np.zeros((b,), bool)
        events = self._window_events(w, no, no, np.zeros((b,), np.float32),
                                     np.zeros((b,), np.int32))
        return self._win_serve.lower(params, self._carry(),
                                     jnp.zeros((w, b), jnp.int32), events,
                                     do_sample=do_sample)

    def reset(self, active: bool = True) -> None:
        """Fresh serving state (empty pool, zeroed clock/reports/sampling
        carry) without dropping the compiled programs — shapes are
        geometry-only, so benchmarks and multi-request drivers restart
        instantly. `active=False` starts every lane empty (the
        continuous-batching driver admits lanes through window
        events)."""
        self.state = kvc.init(self.kv_cfg, backend=self.backend,
                              active=active)
        if self.model.cfg.held_experts:
            self.state["expert_picks"] = jnp.zeros((), jnp.int32)
        self._steps = 0
        self._last_tok = jnp.zeros((self.cfg.batch,), jnp.int32)
        self._key = jax.random.PRNGKey(0)
        self._temp = jnp.zeros((self.cfg.batch,), jnp.float32)  # greedy
        self._topk = jnp.zeros((self.cfg.batch,), jnp.int32)
        self._sample_in_scan = False        # static program variant
        self.reports = []
        self.serve_log = []
        self.dispatches = 0                 # host-side dispatch count

    # -- metrics -----------------------------------------------------------------
    def kv_rss_bytes(self) -> float:
        return float(pl.rss_bytes(self.kv_cfg.pool_config(),
                                  self.state["pool"]))

    def kv_live_bytes(self) -> float:
        """Bytes of LIVE KV objects (allocated blocks x slot bytes) —
        the floor `kv_rss_bytes` reaches at zero fragmentation; the gap
        between the two is what the collector + backend reclaim."""
        n = int(_live_blocks(self.state))
        return float(n * self.kv_cfg.pool_config().slot_bytes)
