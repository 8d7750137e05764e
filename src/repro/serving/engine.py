"""Verification engine (paper §4.5): batched one-step verification.

Two cache backends, auto-selected per family (DESIGN.md §4):

  * paged (attention families: dense/moe/vlm/audio, full attention) —
    sessions allocate fixed-size pages from a shared `PagedKV` pool via a
    block table; prompt prefill fills pages and registers full pages in the
    content-addressed prefix index so concurrent sessions with a common
    prompt prefix share pages; batched verification runs the target once
    over ``[x_last, y_1..y_K]`` through the paged attention kernel with
    per-row block tables and length pointers.  Accepted-length rollback is
    the length pointer plus releasing now-unreachable tail pages.
    Cross-attention K/V (vlm images, audio encoder memory) is bounded and
    stays in a small dense per-slot side cache; prefix sharing is disabled
    for those families (their self-attn KV is not a pure function of the
    token ids).

  * dense slots (recurrent families: ssm/hybrid, plus windowed-attention
    configs) — the engine owns a fixed-capacity cache with ``max_slots``
    rows; sessions map to slots.  Recurrent targets verify through a
    ``lax.scan`` over the K+1 fed tokens that computes the accept rule
    *incrementally* (the accept test at draft position t only needs the
    logits step t produced) and keeps exactly one live "selected state"
    per row — the state at the accepted length — inside the scan carry
    (recurrent state cannot be truncated; DESIGN.md §5).

Hot path (DESIGN.md §9): each ``verify`` batch executes as ONE fused jit
program per (backend, bucket) — cache gather, target forward, the
accept/reject + correction rule, and cache scatter-back all inside the
same dispatch — so only two small ``(B,)`` arrays (``accept_len``,
``token``) return to the host and the ``(B, K+1, V)`` target logits never
leave the device.  Host-side staging uses pooled, bucket-keyed buffers
(no per-call ``np.zeros``/``np.full``); pad rows simply keep the pooled
buffers' reset state — slot index ``max_slots`` is an out-of-bounds
sentinel that gathers clamped (read-only) and whose scatter updates XLA
drops.  ``fed``/``last_token`` commit from one device->host transfer.
The engine counts compiled-program launches (``dispatch_counts``; the
first launch of each program and bucket also in
``stats["cold_dispatches"]``) and staged bytes (``stats``) so
benchmarks/hotpath.py and CI can hold the dispatch/byte budgets.

With a `Tracer` (``tracer=``, `repro.serving.tracing`) the engine records
``wisp.verify`` (staging through the result on the host: ``t_verify``)
with its ``.stage``/``.launch``/``.wait`` children, then
``wisp.verify.commit``; and ``wisp.prefill`` (``t_chunk``) with
``wisp.prefill.stage``/``.launch``/``.wait``.  The programs have stable
names: ``jit_fused_verify`` (paged verify), ``jit_prefill_paged``,
``jit_dense_verify`` and ``jit_recurrent_verify``.

Batch shapes are padded to fixed buckets (draft length to k_max, batch to
powers of two) so jit compiles a bounded set of programs.

Prompt prefill is **incremental** (DESIGN.md §8): ``begin_prefill`` opens a
session without running the model, ``prefill_chunk`` advances it by a
bounded number of prompt tokens, and the prompt's first response token is
produced by whichever chunk consumes the final prompt position.
``new_session`` is the run-to-completion wrapper (one whole-prompt chunk —
the legacy monolithic path, bit-for-bit).  ``step`` executes a mixed batch
of verification items and prefill chunks in one engine dispatch, which is
what lets the SLO scheduler interleave cold-prompt prefill with
deadline-critical verification instead of stalling behind it.
"""
from __future__ import annotations

import dataclasses
from collections import Counter
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.speculative import (
    CompactQ,
    accept_draws,
    correction_token,
    residual_qhat_compact,
    residual_qhat_dense,
    verify_epoch_rule,
)
from repro.models import build, encdec, transformer
from repro.serving.kv_cache import (
    PAGE_SIZE,
    OutOfPages,
    PagedKV,
    TierConfig,
)
from repro.serving.tracing import span, timed

#: families whose self-attn KV can be paged; recurrent state cannot.
ATTENTION_FAMILIES = ("dense", "moe", "vlm", "audio")


class NoFreeSlots(RuntimeError):
    """All ``max_slots`` session rows are occupied (admission-control
    signal, like ``OutOfPages`` for page capacity)."""


def supports_paged(cfg) -> bool:
    """Paged verification needs full (non-windowed) softmax attention —
    the paged kernel addresses history purely through block table +
    length pointer; a sliding-window mask would need per-page offsets."""
    return cfg.family in ATTENTION_FAMILIES and not cfg.sliding_window


def _batch_axis_tree(cache_axes_tree):
    """Map each cache leaf's logical axes -> index of 'act_batch'."""
    return jax.tree.map(
        lambda axes: axes.index("act_batch"),
        cache_axes_tree,
        is_leaf=lambda x: isinstance(x, tuple),
    )


def _bucket(n: int, lo: int = 1) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


def fused_paged_verify(decode_raw):
    """The paged backend's fused verify program: ``decode_raw`` (a paged
    decode with the config bound) over the verify block, then the accept
    rule — one jit dispatch (module ``jit_fused_verify``) returning
    ``(accept_len, token, (k_pages, v_pages))``.  The page pools (args
    2/3) are donated so XLA updates them in place instead of copying the
    whole pool every epoch."""
    def fused_verify(params, feed, kp, vp, bt, base, tl, cross, draft, dlen,
                     rng, tags, qargs, *, method, tagged):
        logits, (kp, vp) = decode_raw(params, feed, kp, vp, bt, base,
                                      tl, cross)
        with jax.named_scope("accept_rule"):
            out = verify_epoch_rule(
                rng, draft, dlen, logits, method=method,
                rng_tags=tags if tagged else None, **qargs,
            )
        return out["accept_len"], out["token"], (kp, vp)

    return jax.jit(fused_verify, static_argnames=("method", "tagged"),
                   donate_argnums=(2, 3))


def paged_prefill(decode_raw):
    """The paged backend's prefill program (module ``jit_prefill_paged``):
    ``decode_raw`` over a batch of prompt chunks, returning ``(first,
    (k_pages, v_pages))``, where ``first[b]`` is the greedy token after
    row b's last new token (position ``t_lens[b] - 1``): the first token
    of a prompt that the chunk finishes.  The ``(B, T, V)`` logits stay
    inside the program, so no second program reads them and no buffer of
    that size outlives it.  The page pools (args 2/3) are donated so XLA
    updates pages in place instead of copying the whole pool (and
    transiently doubling KV memory) every call; the CPU ignores it."""
    def prefill_paged(params, feed, kp, vp, bt, base, tl, cross):
        logits, pools = decode_raw(params, feed, kp, vp, bt, base, tl, cross)
        # a masked sum over T picks each row's last position exactly, and
        # the compiler fuses it into the unembedding, so the logits are
        # never stored (for a v5e, a gather here kept them and copied
        # slices of them besides)
        last = jnp.arange(logits.shape[1])[None, :] == (tl - 1)[:, None]
        row = jnp.where(last[..., None], logits, 0.0).sum(axis=1)
        return jnp.argmax(row, axis=-1), pools

    return jax.jit(prefill_paged, donate_argnums=(2, 3))


def fused_paged_verify_specs(cfg, *, batch: int, k: int, n_pages: int,
                             n_max: int, top_c: int,
                             page_size: int = PAGE_SIZE,
                             cache_dtype=jnp.float32, sharding=None):
    """Shape stand-ins for every positional argument of the dense/moe
    ``fused_paged_verify`` program in compact-q residual mode, as the
    engine stages them: lower and compile it for a described chip without
    allocating the model (``sharding`` places each stand-in)."""
    S = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                  sharding=sharding)
    params = jax.tree.map(
        lambda a: S(a.shape, a.dtype),
        jax.eval_shape(build(cfg).init, jax.random.PRNGKey(0)),
    )
    pool = S((cfg.n_layers, n_pages, cfg.n_kv_heads, page_size,
              cfg.resolved_head_dim), cache_dtype)
    i32, f32 = jnp.int32, jnp.float32
    qargs = {
        "logq_tok": S((batch, k), f32),
        "top_idx": S((batch, k, top_c), i32),
        "top_logq": S((batch, k, top_c), f32),
        "tail": S((batch, k), f32),
    }
    return (params, S((batch, k + 1), i32), pool, pool,
            S((batch, n_max), i32), S((batch,), i32), S((batch,), i32),
            None, S((batch, k), i32), S((batch,), i32),
            S((2,), jnp.uint32), S((batch, 2), i32), qargs)


def _log_softmax1(x):
    """log_softmax at temperature 1.0, matching `speculative._log_softmax`
    bit-for-bit (x / max(1.0, eps) == x exactly)."""
    return jax.nn.log_softmax(x, axis=-1)


@dataclasses.dataclass
class VerifyItem:
    slot: int
    draft_tokens: np.ndarray     # (k,) int32
    #: dense (k, V) float32 draft logits — the exact-residual wire format.
    #: Ignored entirely in greedy mode (nothing is staged).
    q_logits: np.ndarray | None = None
    #: compact draft statistics (`CompactQ`, O(k·C)) — exact accept test,
    #: residual correction within the documented bound (DESIGN.md §9).
    #: A batch must be uniformly dense or uniformly compact.
    q_compact: CompactQ | None = None
    #: optional (a, b) int pair keying this row's accept/correction draws
    #: (serving passes (session_id, committed_len)).  When every item in a
    #: batch carries a tag, verification outcomes become a pure function of
    #: (engine seed, tag, tokens, logits) — independent of batch composition
    #: and dispatch order, so differently-batched drivers commit identical
    #: streams.  Untagged batches keep the legacy split-per-call stream.
    rng_tag: tuple | None = None


@dataclasses.dataclass
class VerifyOutcome:
    slot: int
    accept_len: int
    token: int                   # correction / bonus token
    emitted: int                 # accept_len + 1
    #: host seconds of the batch's ``wisp.verify`` span: staging, launch
    #: and the wait for the results.  The wait also covers any prefill
    #: program still running on the device from the step before.
    t_verify: float


@dataclasses.dataclass
class PrefillState:
    """Resumable prompt-prefill progress for one session slot.

    Created by ``begin_prefill`` (which allocates the slot and, on the
    paged backend, reuses any cached prompt prefix); advanced by
    ``prefill_chunk``.  ``done`` counts prompt tokens whose KV/state is
    valid (including the prefix-cache hit), so ``done`` is the request's
    ``cached_len`` when a chunk is priced by the estimator.  A chunk that
    consumes the final prompt position sets ``first_token`` (the response's
    token 0, sampled greedily from the target's own logits)."""

    slot: int
    tokens: np.ndarray           # full prompt, int32
    done: int                    # prompt tokens with valid KV/state
    extras: dict | None = None   # vlm/audio conditioning (first chunk only)
    first_token: int | None = None
    chunks: int = 0              # chunks executed (observability)
    n_cached: int = 0            # prompt tokens served by the prefix cache

    @property
    def total(self) -> int:
        return len(self.tokens)

    @property
    def remaining(self) -> int:
        return self.total - self.done

    @property
    def finished(self) -> bool:
        return self.first_token is not None


@dataclasses.dataclass
class PrefillChunkItem:
    """One schedulable unit of prompt prefill for ``step``: advance
    ``state`` by up to ``n_tokens`` prompt tokens."""

    state: PrefillState
    n_tokens: int


@dataclasses.dataclass
class PrefillOutcome:
    slot: int
    processed: int               # prompt tokens consumed by this chunk
    done: int                    # total valid prompt tokens after the chunk
    total: int                   # prompt length
    first_token: int | None      # set when the prompt completed this chunk
    #: host seconds of the pass's ``wisp.prefill`` span: staging and the
    #: asynchronous launch (not the program's run), plus the wait for the
    #: first tokens when a prompt finished in the pass
    t_chunk: float
    oom: bool = False            # chunk deferred: page pool cannot cover it


class VerificationEngine:
    def __init__(
        self,
        cfg,
        params,
        *,
        max_slots: int,
        max_len: int,
        method: str = "residual",
        seed: int = 0,
        cache_dtype=jnp.float32,
        paged: bool | None = None,
        page_size: int = PAGE_SIZE,
        n_pages: int | None = None,
        kv_tier_pages: int = 0,
        spill_quantize: bool = False,
        spill_idle_epochs: int = 2,
        device=None,
        tracer=None,
    ):
        self.cfg = cfg
        #: `repro.serving.tracing.Tracer` or None (no spans recorded)
        self.tracer = tracer
        #: the device holding this engine's pools, staging and rng keys
        #: (None: JAX's default device); params must already live there
        self.device = device
        self.bundle = build(cfg)
        self.params = params
        self.max_slots = max_slots
        self.max_len = max_len
        self.method = method
        self.recurrent = cfg.family in ("ssm", "hybrid")
        self.paged = supports_paged(cfg) if paged is None else bool(paged)
        if self.paged and not supports_paged(cfg):
            raise ValueError(
                f"paged verification unsupported for {cfg.name!r} "
                f"(family={cfg.family}, window={cfg.sliding_window})"
            )
        self.fed = np.zeros(max_slots, np.int64)        # KV-valid tokens/slot
        self.last_token = np.zeros(max_slots, np.int64) # committed[-1]/slot
        self.free_slots = list(range(max_slots - 1, -1, -1))
        self.rng = self._put(jax.random.PRNGKey(seed))
        #: never advanced: base for rng_tag-keyed (deterministic) verification
        self._rng_base = self._put(jax.random.PRNGKey(seed))
        #: pooled, bucket-keyed host staging buffers (DESIGN.md §9): one
        #: allocation per (shape bucket, q representation), reused across
        #: calls.  Rows written by the previous call are reset to their pad
        #: value on reuse; pad rows beyond the live batch simply keep that
        #: reset state (slot sentinel ``max_slots``: clamped gather,
        #: dropped scatter — no per-pad-row Python work).
        self._pools: dict[tuple, dict] = {}
        #: compiled-program launches by name ("verify" is the fused
        #: per-epoch program — exactly one per verify() call, any backend)
        self.dispatch_counts: Counter = Counter()
        #: (program, bucket) keys launched so far (``cold_dispatches``)
        self._launched: set = set()
        #: ``prefix_cached_tokens`` counts prompt tokens satisfied by the
        #: content-addressed prefix cache.  That cache exists only on the
        #: paged backend — on the dense backend the field is structurally
        #: zero (no prefix cache, nothing to hit), not "zero hits observed";
        #: check ``stats["backend"]`` / ``prefix_cache_stats()["backend"]``
        #: before reading it as a hit rate (DESIGN.md §3).
        self.stats = {
            "backend": "paged" if self.paged else "dense",
            "batches": 0,
            "tokens_verified": 0,
            "tokens_committed": 0,
            "prefix_cached_tokens": 0,
            "prefill_chunks": 0,
            #: launches of a (program, bucket) not launched before: each
            #: compiled or loaded a program, unless an earlier engine in
            #: the process had
            "cold_dispatches": 0,
            "h2d_bytes": 0,           # host->device staged bytes (verify)
            "h2d_q_bytes": 0,         # ...of which draft-q payload
            "d2h_bytes": 0,           # device->host result bytes (verify)
            #: batches whose rows carried heterogeneous draft lengths
            #: (adaptive per-session K, DESIGN.md §11): ragged rows ride
            #: the existing bucket/pad machinery — per-row ``dlen`` masks
            #: the pad tail, so mixed-K costs no extra dispatch
            "mixed_k_batches": 0,
            #: MoE targets, counted on the host from each program's shapes:
            #: routed (token, expert) assignments of real tokens (tokens x
            #: top-k x layers), and expert-layer passes (calls x layers)
            "moe_assignments": 0,
            "moe_expert_passes": 0,
            #: host spill tier (DESIGN.md §12): bytes moved across the
            #: device<->host boundary by spill / page-in, plus format
            #: counters — structurally zero when no tier is configured
            "spill_bytes": 0,
            "pagein_bytes": 0,
            "pages_spilled": 0,
            "pages_paged_in": 0,
            "spills_quantized": 0,
            "spills_raw": 0,
            "host_evictions": 0,
        }
        if kv_tier_pages > 0 and not self.paged:
            raise ValueError(
                "kv_tier_pages requires the paged backend "
                f"(family={cfg.family}, window={cfg.sliding_window})"
            )
        self._tier_cfg = (
            TierConfig(host_pages=int(kv_tier_pages),
                       quantize=bool(spill_quantize),
                       idle_epochs=int(spill_idle_epochs))
            if kv_tier_pages > 0 else None
        )

        if self.paged:
            self._init_paged(cache_dtype, page_size, n_pages)
        else:
            self.cache = self.bundle.init_cache(max_slots, max_len, dtype=cache_dtype) \
                if cfg.family != "ssm" else self.bundle.init_cache(max_slots, max_len)
            if self.device is not None:
                self.cache = jax.device_put(self.cache, self.device)
            self._bax = _batch_axis_tree(self.bundle.cache_axes())
            self._decode = jax.jit(self.bundle.decode)
            self._prefill = jax.jit(self.bundle.prefill)
            self._fused_verify = (
                self._build_fused_recurrent()
                if self.recurrent
                else self._build_fused_attention()
            )

    # -- paged backend setup --------------------------------------------------
    def _init_paged(self, cache_dtype, page_size, n_pages):
        cfg = self.cfg
        hkv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
        if n_pages is None:
            # every slot must be able to reach max_len even with per-slot
            # page rounding, + the reserved scratch page
            n_pages = self.max_slots * -(-self.max_len // page_size) + 1
        self.page_size = page_size
        self.kv = PagedKV(
            cfg.n_layers, n_pages, hkv, hd,
            page_size=page_size, dtype=cache_dtype,
            tier=self._tier_cfg, counters=self.stats, device=self.device,
        )
        #: prefix sharing is sound only when KV is a pure function of the
        #: token ids — cross-attention families condition on extras.
        self.share_prefix = cfg.family in ("dense", "moe")
        self.tokens: dict[int, list] = {}   # slot -> tokens with KV in pages
        self.extras_cache = None
        if cfg.family == "vlm":
            n_groups = cfg.n_layers // cfg.cross_attn_every
            z = lambda: jnp.zeros(
                (n_groups, self.max_slots, cfg.num_image_tokens, hkv, hd),
                cache_dtype, device=self.device,
            )
            self.extras_cache = {"k_img": z(), "v_img": z()}
            self._extras_key = "image_embeds"
            self._extras_builder = jax.jit(partial(transformer.vlm_cross_kv, cfg))
            decode_raw = partial(transformer.decode_paged, cfg)
        elif cfg.family == "audio":
            z = lambda: jnp.zeros(
                (cfg.n_layers, self.max_slots, cfg.encoder_frames, hkv, hd),
                cache_dtype, device=self.device,
            )
            self.extras_cache = {"k_mem": z(), "v_mem": z()}
            self._extras_key = "frames"
            self._extras_builder = jax.jit(partial(encdec.encdec_cross_kv, cfg))
            decode_raw = partial(encdec.encdec_decode_paged, cfg)
        else:
            decode_raw = partial(transformer.decode_paged, cfg)
        self._prefill_paged = paged_prefill(decode_raw)
        self._fused_verify = fused_paged_verify(decode_raw)

    # -- fused per-epoch verify programs (DESIGN.md §9) -----------------------
    # Each program is ONE jit dispatch: target forward + the accept/reject
    # + correction rule, returning just (accept_len, token) plus the
    # updated device-resident cache state.  ``qargs`` is a (possibly empty)
    # dict of staged draft-q arrays whose structure selects the dense /
    # compact / greedy variant at trace time.

    def _build_fused_attention(self):
        decode = self.bundle.decode
        bax = self._bax

        def dense_verify(params, cache, slot_idx, feed, pos, draft, dlen,
                         rng, tags, qargs, *, method, tagged):
            sub = jax.tree.map(
                lambda leaf, ax: jnp.take(leaf, slot_idx, axis=ax,
                                          mode="clip"),
                cache, bax,
            )
            p_logits, sub = decode(params, feed, sub, pos)
            with jax.named_scope("accept_rule"):
                out = verify_epoch_rule(
                    rng, draft, dlen, p_logits, method=method,
                    rng_tags=tags if tagged else None, **qargs,
                )

            def put(leaf, new, ax):
                sl = (slice(None),) * ax
                # pad rows carry the OOB slot sentinel: XLA drops their
                # updates, so no masking / per-row host logic is needed
                return leaf.at[sl + (slot_idx,)].set(new.astype(leaf.dtype))

            cache = jax.tree.map(put, cache, sub, bax)
            return out["accept_len"], out["token"], cache

        return jax.jit(dense_verify, static_argnames=("method", "tagged"),
                       donate_argnums=(1,))

    def _build_fused_recurrent(self):
        decode = self.bundle.decode
        bax = self._bax
        V = self.cfg.vocab

        def tree_where(cond, new, old):
            def w(nl, ol, ax):
                shape = [1] * nl.ndim
                shape[ax] = cond.shape[0]
                return jnp.where(cond.reshape(shape), nl, ol)

            return jax.tree.map(w, new, old, bax)

        def recurrent_verify(params, cache, slot_idx, feed, pos, draft, dlen,
                             rng, tags, qargs, *, method, tagged):
            B, T = feed.shape
            K = T - 1
            sub = jax.tree.map(
                lambda leaf, ax: jnp.take(leaf, slot_idx, axis=ax,
                                          mode="clip"),
                cache, bax,
            )
            rng_tags = tags if tagged else None
            u, row_keys, rng = accept_draws(rng, B, K, method, rng_tags)
            logq_full = None
            if "q_logits" in qargs:
                logq_full = _log_softmax1(qargs["q_logits"])
                lqt = jnp.take_along_axis(
                    logq_full, draft[..., None], axis=-1
                )[..., 0]
            elif "logq_tok" in qargs:
                lqt = qargs["logq_tok"]
            else:
                lqt = jnp.zeros((B, K), jnp.float32)     # greedy: unused

            # Per-step inputs, padded to T steps.  Step t feeds feed[:, t]
            # and its output logits are the target distribution for draft
            # position t — so the accept test runs INSIDE the scan, the
            # carry tracks the still-accepting prefix, and the state at the
            # accepted length is selected as it streams past (one live
            # state copy instead of T+1 stacked caches).  Step K is the
            # bonus slot (its logits are the bonus distribution).
            kpos = jnp.arange(K, dtype=jnp.int32)
            xs = dict(
                tok=feed.T,
                t=jnp.arange(T, dtype=jnp.int32),
                d=jnp.pad(draft, ((0, 0), (0, 1))).T,
                val=jnp.pad(kpos[None, :] < dlen[:, None],
                            ((0, 0), (0, 1))).T,
                bon=(jnp.arange(T, dtype=jnp.int32)[None, :]
                     == dlen[:, None]).T,
                u=jnp.pad(jnp.ones((B, K)) if u is None else u,
                          ((0, 0), (0, 1)), constant_values=1.0).T,
                lq=jnp.pad(lqt, ((0, 0), (0, 1))).T,
            )

            def body(carry, x):
                state, kept, still, corr, L = carry
                lg, state = decode(params, x["tok"][:, None], state,
                                   pos + x["t"])
                row = lg[:, 0]
                # rows whose accepted prefix is still growing (still was
                # True *entering* this step) advance their selected state;
                # the step after a row's stop (rejection or bonus) — and
                # every later one — leaves it frozen at length L+1
                kept = tree_where(still, state, kept)
                if method == "greedy":
                    acc_raw = x["d"] == jnp.argmax(row, axis=-1).astype(
                        x["d"].dtype
                    )
                else:
                    lpt = jnp.take_along_axis(
                        _log_softmax1(row), x["d"][:, None], axis=-1
                    )[:, 0]
                    acc_raw = jnp.log(x["u"]) <= (lpt - x["lq"])
                stop = jnp.logical_and(
                    still,
                    jnp.logical_or(
                        x["bon"],
                        jnp.logical_and(x["val"], jnp.logical_not(acc_raw)),
                    ),
                )
                corr = jnp.where(stop[:, None], row.astype(corr.dtype), corr)
                L = L + jnp.logical_and(
                    still, jnp.logical_and(x["val"], acc_raw)
                ).astype(jnp.int32)
                still = jnp.logical_and(still, jnp.logical_not(stop))
                return (state, kept, still, corr, L), None

            # the scan carry must be type-stable: decode may return state
            # in a wider dtype than the stored cache (e.g. bf16 conv
            # buffers stepping in f32) — initialize the carry in decode's
            # OUTPUT dtypes (exact upcast) and cast back at scatter
            out_aval = jax.eval_shape(
                lambda s: decode(params, feed[:, :1], s, pos)[1], sub
            )
            sub0 = jax.tree.map(
                lambda leaf, a: leaf.astype(a.dtype), sub, out_aval
            )
            init = (
                sub0, sub0, jnp.ones((B,), bool),
                jnp.zeros((B, V), jnp.float32),
                jnp.zeros((B,), jnp.int32),
            )
            (_, kept, _, corr, L), _ = jax.lax.scan(body, init, xs)

            qhat = None
            if method == "residual":
                if logq_full is not None:
                    qhat = residual_qhat_dense(logq_full, L)
                else:
                    qhat = residual_qhat_compact(
                        qargs["top_idx"], qargs["top_logq"], qargs["tail"],
                        L, V,
                    )
            token, rng = correction_token(
                rng, row_keys, corr, qhat, method=method, temperature=1.0
            )

            def put(leaf, new, ax):
                sl = (slice(None),) * ax
                return leaf.at[sl + (slot_idx,)].set(new.astype(leaf.dtype))

            cache = jax.tree.map(put, cache, kept, bax)
            return L, token.astype(jnp.int32), cache

        return jax.jit(recurrent_verify, static_argnames=("method", "tagged"),
                       donate_argnums=(1,))

    def _put(self, x, dtype=None):
        """Host data onto this engine's device (JAX's default if unplaced)."""
        return jnp.asarray(x, dtype, device=self.device)

    # -- slot/cache plumbing (dense backend) ----------------------------------
    def _gather(self, slots):
        idx = self._put(slots, jnp.int32)
        return jax.tree.map(
            lambda leaf, ax: jnp.take(leaf, idx, axis=ax, mode="clip"),
            self.cache, self._bax,
        )

    def _scatter(self, slots, sub, valid_n):
        idx = np.asarray(slots[:valid_n], np.int32)

        def put(leaf, new, ax):
            sl = (slice(None),) * ax
            return leaf.at[sl + (idx,)].set(
                jax.lax.slice_in_dim(new, 0, valid_n, axis=ax).astype(leaf.dtype)
            )

        self.cache = jax.tree.map(put, self.cache, sub, self._bax)

    # -- extras side cache (paged vlm/audio: batch axis is 1) -----------------
    def _extras_gather(self, slots):
        idx = self._put(slots, jnp.int32)
        return jax.tree.map(
            lambda leaf: jnp.take(leaf, idx, axis=1, mode="clip"),
            self.extras_cache,
        )

    def _extras_put(self, slot, sub):
        self.extras_cache = jax.tree.map(
            lambda leaf, new: leaf.at[:, slot].set(new[:, 0].astype(leaf.dtype)),
            self.extras_cache, sub,
        )

    # -- staging pools + dispatch accounting ----------------------------------
    def _count_dispatch(self, name: str, bucket: tuple = ()):
        self.dispatch_counts[name] += 1
        if (name, bucket) not in self._launched:
            self._launched.add((name, bucket))
            self.stats["cold_dispatches"] += 1

    def _count_moe(self, tokens: int):
        """One target call over ``tokens`` real tokens, in the MoE counters."""
        if self.cfg.moe is not None:
            L = self.cfg.n_layers
            self.stats["moe_assignments"] += tokens * self.cfg.moe.top_k * L
            self.stats["moe_expert_passes"] += L

    def _pool(self, key: tuple, spec: dict) -> dict:
        """Fetch (or build) the pooled buffer set for ``key``.  On reuse,
        rows the previous call wrote (tracked by the ``_hw`` high-water
        mark) are reset to their pad value — pad rows of the next batch
        need no per-row Python work, they just keep this state."""
        p = self._pools.get(key)
        if p is None:
            p = {"_hw": 0}
            for name2, (shape, dtype, fill) in spec.items():
                p[name2] = np.full(shape, fill, dtype) if fill else \
                    np.zeros(shape, dtype)
            self._pools[key] = p
            return p
        hw = p["_hw"]
        if hw:
            for name2, (shape, dtype, fill) in spec.items():
                p[name2][:hw] = fill if fill else 0
        return p

    def _stage_verify(self, nb: int, K: int, q_kind: str, C: int) -> dict:
        sent = self.max_slots                  # OOB slot sentinel (pad rows)
        spec = {
            "draft": ((nb, K), np.int32, 0),
            "dlen": ((nb,), np.int32, 0),
            "feed": ((nb, K + 1), np.int32, 0),
            "pos": ((nb,), np.int32, 0),
            "slots": ((nb,), np.int32, sent),
            "tags": ((nb, 2), np.int32, 0),
            "tl": ((nb,), np.int32, 0),
        }
        if q_kind == "dense":
            spec["qlog"] = ((nb, K, self.cfg.vocab), np.float32, -30.0)
        elif q_kind == "compact":
            spec["logq_tok"] = ((nb, K), np.float32, 0)
            # unused table cells carry an out-of-vocab id: their scatter
            # updates are dropped during q̂ reconstruction — an in-bounds
            # pad (e.g. 0) would collide with token 0's real top entry
            # when blocks of different C share a batch bucket
            spec["top_idx"] = ((nb, K, C), np.int32, 1 << 30)
            spec["top_logq"] = ((nb, K, C), np.float32, -30.0)
            spec["tail"] = ((nb, K), np.float32, 0)
        return self._pool(("verify", nb, K, q_kind, C), spec)

    # -- memory accounting ----------------------------------------------------
    def memory_budget_tokens(self) -> int:
        """KV-token capacity the scheduler may admit against this epoch.

        A scheduled request accounts ``cached_len + new_tokens``; its
        cached tokens are covered by its session's resident pages and its
        new tokens must fit in its own tail-page slack or in pages the
        allocator can still hand out (free + evictable prefix-cached).  So
        the live budget is ``resident_capacity + free`` — counting the
        slack inside sequences' tail pages matters: with large pages and
        short sessions most capacity *is* tail slack, and a budget of only
        committed+free livelocks a full pool even though every request
        fits (single-slot engines hit this immediately).  The budget
        tightens as rejected-draft garbage accumulates and widens when
        sessions close or tail pages are trimmed.  The dense backend's
        capacity is static.

        With a spill tier (DESIGN.md §12) the budget additionally counts
        tokens the tier could move to host DRAM on demand (cold private
        pages of idle sessions, capped by host headroom) — admission sees
        through the tier, which is what multiplies resident-session
        capacity past the device pool."""
        if self.paged:
            return (self.kv.free_tokens + self.kv.resident_tokens()
                    + self.kv.spillable_tokens())
        return self.max_slots * self.max_len

    # -- spill tier (DESIGN.md §12) -------------------------------------------
    @property
    def tiered(self) -> bool:
        return self.paged and self.kv.tiered

    def spill_session(self, slot: int) -> int:
        """Force-spill a session's private pages to the host tier (tests,
        golden-stream battery, and explicit cold-session demotion).
        Returns device pages freed; 0 without a tier."""
        if not self.tiered:
            return 0
        return self.kv.spill_seq(slot)

    def prefetch_session(self, slot: int) -> int:
        """Best-effort page-in of a session's spilled pages ahead of its
        next verify epoch (the server calls this at submit time so the
        fused hot path never blocks on a fault).  Returns pages loaded; a
        device pool too full to cover the prefetch leaves the session
        spilled — verify's own ``ensure_resident`` retries under the
        OutOfPages degradation path."""
        if not self.tiered or slot not in self.kv.tables:
            return 0
        try:
            return self.kv.ensure_resident(slot)
        except OutOfPages:
            return 0

    def spilled_tokens(self, slot: int) -> int:
        """Token capacity of ``slot``'s host-resident pages — the page-in
        debt a verify of this session must pay (the scheduler prices it
        via ``WorkItem.pagein_tokens``)."""
        if not self.tiered or slot not in self.kv.tables:
            return 0
        return self.kv.spilled_tokens(slot)

    def prefix_cache_stats(self) -> dict:
        """Prefix-cache / page-pool counters, tagged with the backend that
        produced them.  The prefix cache is a paged-backend structure; the
        dense backend reports ``backend="dense"`` with zero counters —
        structurally zero (the cache does not exist), not a measured 0%
        hit rate.  Callers comparing backends must branch on ``backend``
        instead of treating the zeros as data (DESIGN.md §3)."""
        if self.paged:
            a = self.kv.allocator
            return {"backend": "paged", "hits": a.hits, "misses": a.misses,
                    "pages_in_use": a.in_use, "pages_free": len(a.free)}
        return {"backend": "dense", "hits": 0, "misses": 0,
                "pages_in_use": 0, "pages_free": 0}

    # -- session lifecycle -----------------------------------------------------
    def new_session(self, prompt_tokens, extras=None) -> tuple[int, int]:
        """Prefill a prompt into a fresh slot.  Returns (slot, first_token).

        Monolithic wrapper over the incremental path: one whole-prompt
        chunk, so behavior (including jit bucketing) is identical to the
        legacy blocking prefill.  The first committed token is sampled from
        the target's own prefill logits (the response's token 0 always
        comes from the target).  Paged backend: raises ``OutOfPages`` (with
        the slot and any partial pages returned) when the pool cannot cover
        the prompt."""
        st = self.begin_prefill(prompt_tokens, extras=extras)
        try:
            while not st.finished:
                self.prefill_chunk(st, st.remaining)
        except OutOfPages:
            self.abort_prefill(st)
            raise
        return st.slot, st.first_token

    def begin_prefill(self, prompt_tokens, extras=None) -> PrefillState:
        """Open a session slot for incremental prompt prefill WITHOUT
        running the model.  Paged backend: allocates the block table and
        reuses any content-addressed cached prefix (``state.done`` starts
        at the prefix hit) and builds the bounded cross-attention side
        cache for vlm/audio extras.  Raises ``NoFreeSlots`` /
        ``OutOfPages`` with nothing leaked (admission-control signals)."""
        if not self.free_slots:
            raise NoFreeSlots("no free verification slots")
        toks = np.asarray(prompt_tokens, np.int32)
        slot = self.free_slots.pop()
        if not self.paged:
            return PrefillState(slot=slot, tokens=toks, done=0, extras=extras)
        try:
            n_cached = self.kv.open_seq(slot, toks, share=self.share_prefix)
        except OutOfPages:
            if slot in self.kv.tables:
                self.kv.close_seq(slot)
            self.free_slots.append(slot)
            raise
        if self.extras_cache is not None:
            x = extras[self._extras_key]
            self._count_dispatch("extras", np.shape(x))
            k_x, v_x = self._extras_builder(self.params, self._put(x))
            keys = sorted(self.extras_cache)          # (k_img, v_img) / (k_mem, v_mem)
            self._extras_put(slot, {keys[0]: k_x, keys[1]: v_x})
        return PrefillState(slot=slot, tokens=toks, done=n_cached,
                            extras=extras, n_cached=n_cached)

    def prefill_chunk(self, st: PrefillState, n_tokens: int) -> int:
        """Advance ``st`` by up to ``n_tokens`` prompt tokens in one forward
        pass; returns the tokens consumed.  The chunk that consumes the
        final prompt position samples the first response token and (paged,
        sharing families) publishes the prompt's full pages to the prefix
        index.  Paged backend: raises ``OutOfPages`` with ``st`` intact and
        resumable when the pool cannot cover the chunk — retry after pages
        free, or ``abort_prefill``."""
        if st.finished:
            return 0
        n = min(int(n_tokens), st.remaining)
        if n <= 0:
            return 0
        if self.paged:
            self._prefill_chunks_paged([PrefillChunkItem(st, n)],
                                       raise_oom=True)
        else:
            self._prefill_chunk_dense(st, n)
        return n

    def abort_prefill(self, st: PrefillState):
        """Release a partially-prefilled session (slot, pages, block
        table).  Safe at any progress point: the prefix index only ever
        sees *completed* prompts, so nothing published needs retraction."""
        self.close_session(st.slot)

    def _finish_prefill(self, st: PrefillState, first: int):
        slot = st.slot
        st.first_token = first
        self.fed[slot] = st.total
        self.last_token[slot] = first
        if self.paged:
            if self.share_prefix:
                # register NOW (not at close) so concurrent same-prompt
                # sessions share pages
                self.kv.publish_seq_prefix(slot, st.tokens)
            self.tokens[slot] = [int(t) for t in st.tokens]
            self.stats["prefix_cached_tokens"] += int(st.n_cached)

    def _stage_prefill_chunks(self, chunks, raise_oom: bool) -> tuple:
        """Page reservation, pooled staging and the device puts of one
        ragged prefill pass.  Returns the per-chunk ``oom`` flags, the live
        ``(state, n)`` rows, the program bucket and its arguments after the
        params and pools."""
        live: list = []
        oom = [False] * len(chunks)
        if self.tiered:
            # co-scheduled chunks must not spill each other mid-staging
            self.kv.tick()
            for c in chunks:
                if c.state.slot in self.kv.tables:
                    self.kv.touch_seq(c.state.slot)
        for i, c in enumerate(chunks):
            st = c.state
            n = min(int(c.n_tokens), st.remaining)
            if n <= 0:
                continue
            try:
                if self.tiered:
                    # a partially-prefilled session parked behind the
                    # admission queue may have been spilled by reclaim;
                    # restore before reserving the chunk's pages
                    self.kv.ensure_resident(st.slot)
                self.kv.ensure_capacity(st.slot, st.done + n)
            except OutOfPages:
                if raise_oom:
                    raise
                oom[i] = True
                continue
            live.append((st, n))
        if not live:
            return oom, live, None, None
        T = _bucket(max(n for _, n in live), 16)
        nb = _bucket(len(live), 1)
        n_max = _bucket(max(self.kv.seq_pages(st.slot) for st, _ in live), 1)
        bufs = self._pool(("prefill", nb, T, n_max), {
            "feed": ((nb, T), np.int32, 0),
            "base": ((nb,), np.int32, 0),
            "tl": ((nb,), np.int32, 0),
            "slots": ((nb,), np.int32, self.max_slots),
            "bt": ((nb, n_max), np.int32, 0),
        })
        # pad rows: zero block table + zero valid length -> their K/V writes
        # land on the scratch page and their logits are discarded (slot
        # sentinel: extras gather clamps, read-only)
        for i, (st, n) in enumerate(live):
            bufs["feed"][i, :n] = st.tokens[st.done : st.done + n]
            bufs["base"][i] = st.done
            bufs["tl"][i] = n
            bufs["slots"][i] = st.slot
        bufs["bt"][: len(live)] = self.kv.block_table(
            [st.slot for st, _ in live], n_max
        )
        bufs["_hw"] = len(live)
        # the program gets private copies: this call waits on the device
        # only when a prompt finishes, the next one rewrites the pooled
        # buffers, and a CPU device array may alias its numpy source
        staged = {k: bufs[k].copy() for k in ("feed", "bt", "base", "tl", "slots")}
        cross = (
            self._extras_gather(staged["slots"])
            if self.extras_cache is not None else None
        )
        args = [self._put(staged[k]) for k in ("feed", "bt", "base", "tl")]
        return oom, live, (nb, T, n_max), args + [cross]

    def _prefill_chunks_paged(self, chunks, *, raise_oom: bool = False):
        """Execute prefill chunks as rows of ONE ragged ``decode_paged``
        call (the flattened multi-token paged path verification uses — each
        prompt token is its own kernel row with length ``done + t + 1``, so
        chunked and monolithic prefill run the identical per-token
        computation).  Returns per-chunk ``oom`` flags; with ``raise_oom``
        an uncoverable chunk raises instead.  Either way the affected
        state is untouched and resumable."""
        tr = self.tracer
        with span(tr, "wisp.prefill.stage", chunks=len(chunks)):
            oom, live, bucket, args = self._stage_prefill_chunks(chunks,
                                                                 raise_oom)
        if not live:
            return oom
        with span(tr, "wisp.prefill.launch", bucket=bucket):
            self._count_dispatch("prefill", bucket)
            feed_d, bt_d, base_d, tl_d, cross = args
            first_d, (kp, vp) = self._prefill_paged(
                self.params, feed_d, self.kv.k_pages, self.kv.v_pages,
                bt_d, base_d, tl_d, cross,
            )
            self.kv.k_pages, self.kv.v_pages = kp, vp
        self._count_moe(sum(n for _, n in live))
        finished: list = []
        for i, (st, n) in enumerate(live):
            st.done += n
            st.chunks += 1
            self.kv.set_len(st.slot, st.done)
            self.stats["prefill_chunks"] += 1
            if st.remaining == 0:
                finished.append((i, st))
        if finished:
            # ONE transfer of the program's (B,) first tokens for every
            # chunk that completed its prompt this call
            with span(tr, "wisp.prefill.wait", finished=len(finished)):
                firsts = np.asarray(jax.device_get(first_d))
                for i, st in finished:
                    self._finish_prefill(st, int(firsts[i]))
        return oom

    def _prefill_chunk_dense(self, st: PrefillState, n: int):
        """One dense-backend prefill chunk.  The first chunk goes through
        the bundle's ``prefill`` entry point (builds vlm/audio cross-KV;
        keeps the legacy monolithic path bit-identical when the chunk
        covers the whole prompt); resumed chunks feed the cache at position
        ``done`` through ``decode`` — the same cached-attention path
        verification uses.  Attention targets: bucket the chunk so jit
        compiles a bounded set of programs — padded positions are
        stale-but-masked by the length pointer (and overwritten by the next
        chunk).  Recurrent targets: padding would ADVANCE the stored state
        through garbage tokens; run the exact length."""
        if n <= 0:
            return
        s0 = st.done
        chunk = st.tokens[s0 : s0 + n]
        Tb = n if self.recurrent else _bucket(n, 16)
        padded = np.zeros((1, Tb), np.int32)
        padded[0, :n] = chunk
        sub = self._gather([st.slot])
        if s0 == 0:
            batch = {"tokens": self._put(padded)}
            if st.extras:
                batch.update(st.extras)
            self._count_dispatch("prefill", (Tb, True))
            logits, sub = self._prefill(self.params, batch, sub)
        else:
            self._count_dispatch("prefill", (Tb, False))
            logits, sub = self._decode(
                self.params, self._put(padded), sub, jnp.int32(s0)
            )
        self._scatter([st.slot], sub, 1)
        self._count_moe(n)
        st.done += n
        st.chunks += 1
        self.stats["prefill_chunks"] += 1
        if st.remaining == 0:
            self._count_dispatch("first_token", (Tb, n))
            first = int(jax.device_get(jnp.argmax(logits[0, n - 1])))
            self._finish_prefill(st, first)

    def close_session(self, slot: int):
        if self.paged:
            committed = self.tokens.pop(slot, [])
            n_kv = int(self.fed[slot])
            self.kv.close_seq(
                slot, committed[:n_kv] if self.share_prefix else None
            )
        self.fed[slot] = 0
        self.free_slots.append(slot)

    # -- unified dispatch (mixed verify + prefill) ------------------------------
    def step(self, items: list) -> list:
        """Execute one mixed engine dispatch: the batch the SLO scheduler
        admitted for this epoch, containing any mix of ``VerifyItem`` and
        ``PrefillChunkItem``.

        Contract (docs/ARCHITECTURE.md §2):

          * all verification items run as ONE batched ``verify`` call;
          * all prefill chunks run as rows of ONE ragged paged prefill call
            (dense backend: per-slot passes — no shared pool to batch over);
          * outcomes are returned aligned with ``items``
            (``VerifyOutcome`` / ``PrefillOutcome``);
          * ``OutOfPages`` raised by the *verify* portion propagates before
            any device state is touched (the server degrades to per-item
            steps, DESIGN.md §6);
          * a prefill chunk the pool cannot cover does NOT raise: it comes
            back as ``PrefillOutcome(oom=True, processed=0)`` with its
            state intact — requeue it and retry once pages free.
        """
        vidx = [i for i, it in enumerate(items) if isinstance(it, VerifyItem)]
        cidx = [i for i, it in enumerate(items)
                if isinstance(it, PrefillChunkItem)]
        if len(vidx) + len(cidx) != len(items):
            raise TypeError("step items must be VerifyItem or PrefillChunkItem")
        out: list = [None] * len(items)
        for i, o in zip(vidx, self.verify([items[i] for i in vidx])):
            out[i] = o
        if cidx:
            chunks = [items[i] for i in cidx]
            before = [c.state.done for c in chunks]
            # t_chunk: the whole prefill pass, not the verify before it
            with timed(self.tracer, "wisp.prefill") as tp:
                if self.paged:
                    oom = self._prefill_chunks_paged(chunks)
                else:
                    oom = [False] * len(chunks)
                    for c in chunks:
                        self._prefill_chunk_dense(
                            c.state, min(int(c.n_tokens), c.state.remaining)
                        )
            dt = tp.dt
            for i, c, was, o in zip(cidx, chunks, before, oom):
                st = c.state
                out[i] = PrefillOutcome(
                    slot=st.slot,
                    processed=st.done - was,
                    done=st.done,
                    total=st.total,
                    first_token=st.first_token,
                    t_chunk=dt,
                    oom=o,
                )
        return out

    # -- batched verification ---------------------------------------------------
    def verify(self, items: list[VerifyItem]) -> list[VerifyOutcome]:
        """One fused dispatch per batch: stage into pooled buffers, run the
        (backend-specific) fused program, read back two (B,) arrays in one
        transfer, commit ``fed``/``last_token`` vectorized."""
        if not items:
            return []
        tr = self.tracer
        # t_verify: staging through the results on the host
        with timed(tr, "wisp.verify") as tv:
            with span(tr, "wisp.verify.stage"):
                bufs, bucket, launch = self._stage_verify_batch(items)
            with span(tr, "wisp.verify.launch", bucket=bucket):
                self._count_dispatch("verify", bucket)
                acc_d, tok_d = launch()
            with span(tr, "wisp.verify.wait"):
                # ONE device->host transfer carries the whole epoch's results
                acc, tok = jax.device_get((acc_d, tok_d))
            self.stats["d2h_bytes"] += acc.nbytes + tok.nbytes
        with span(tr, "wisp.verify.commit"):
            return self._commit_verify(items, bufs, acc, tok, tv.dt)

    def _stage_verify_batch(self, items: list[VerifyItem]) -> tuple:
        """Pooled staging, page reservation (``OutOfPages`` raises here,
        before any engine side effect), rng, block tables and the device
        puts of one verify batch.  Returns its buffers, its program bucket
        and the call that launches the fused program."""
        n = len(items)
        dlens = {len(it.draft_tokens) for it in items}
        if len(dlens) > 1:
            self.stats["mixed_k_batches"] += 1
        K = _bucket(max(max(dlens), 1), 2)
        nb = _bucket(n, 1)

        if self.method == "greedy":
            # greedy verification never reads q: nothing is staged at all
            q_kind, C = "none", 0
        elif all(it.q_compact is not None for it in items):
            q_kind = "compact"
            C = max(1, max(it.q_compact.C for it in items))
        else:
            if any(it.q_compact is not None for it in items):
                raise ValueError(
                    "a verify batch must be uniformly dense-q or "
                    "uniformly compact-q"
                )
            q_kind, C = "dense", 0

        bufs = self._stage_verify(nb, K, q_kind, C)
        for i, it in enumerate(items):
            k = len(it.draft_tokens)
            bufs["draft"][i, :k] = it.draft_tokens
            bufs["dlen"][i] = k
            bufs["tl"][i] = k + 1
            bufs["feed"][i, 0] = self.last_token[it.slot]
            bufs["feed"][i, 1 : 1 + k] = it.draft_tokens
            bufs["pos"][i] = self.fed[it.slot]
            bufs["slots"][i] = it.slot
            if q_kind == "dense":
                if it.q_logits is not None and it.q_logits.size:
                    bufs["qlog"][i, :k] = it.q_logits
            elif q_kind == "compact":
                q = it.q_compact
                c = q.C
                bufs["logq_tok"][i, :k] = q.logq_tok
                bufs["top_idx"][i, :k, :c] = q.top_idx
                bufs["top_logq"][i, :k, :c] = q.top_logq
                bufs["tail"][i, :k] = q.tail
        bufs["_hw"] = n

        if self.paged:
            # reserve pages FIRST: OutOfPages must propagate before any
            # engine side effect (rng split, byte counters) so an
            # OOM-requeued batch replays identically and is not
            # double-counted (staging pools alone are reset-on-reuse).
            # With a spill tier, first mark every batch row live (so one
            # row's page-in cannot spill a co-scheduled row), then page
            # spilled rows back in — page-ins that land stay resident
            # across an OOM requeue, so the replay is a no-op for them.
            if self.tiered:
                self.kv.tick()
                for it in items:
                    self.kv.touch_seq(it.slot)
                for it in items:
                    self.kv.ensure_resident(it.slot)
            for it in items:
                self.kv.ensure_capacity(
                    it.slot,
                    int(self.fed[it.slot]) + len(it.draft_tokens) + 1,
                )

        tagged = all(it.rng_tag is not None for it in items)
        if tagged:
            for i, it in enumerate(items):
                bufs["tags"][i] = it.rng_tag
            kv = self._rng_base
        else:
            self.rng, kv = jax.random.split(self.rng)

        qargs = {}
        q_bytes = 0
        if q_kind == "dense":
            qargs["q_logits"] = self._put(bufs["qlog"])
            q_bytes = bufs["qlog"].nbytes
        elif q_kind == "compact":
            for name in ("logq_tok", "top_idx", "top_logq", "tail"):
                qargs[name] = self._put(bufs[name])
                q_bytes += bufs[name].nbytes
        core_bytes = (bufs["draft"].nbytes + bufs["dlen"].nbytes
                      + bufs["feed"].nbytes + bufs["pos"].nbytes
                      + bufs["tags"].nbytes)
        self.stats["h2d_bytes"] += core_bytes + q_bytes
        self.stats["h2d_q_bytes"] += q_bytes

        draft_d = self._put(bufs["draft"])
        dlen_d = self._put(bufs["dlen"])
        feed_d = self._put(bufs["feed"])
        tags_d = self._put(bufs["tags"])

        if self.paged:
            n_max = _bucket(max(self.kv.seq_pages(it.slot) for it in items),
                            1)
            btb = self._pool(("bt", nb, n_max), {
                "bt": ((nb, n_max), np.int32, 0),
            })
            btb["bt"][:n] = self.kv.block_table([it.slot for it in items],
                                                n_max)
            btb["_hw"] = n
            self.stats["h2d_bytes"] += btb["bt"].nbytes + bufs["tl"].nbytes
            cross = (
                self._extras_gather(bufs["slots"])
                if self.extras_cache is not None else None
            )
            bt_d, pos_d, tl_d = (self._put(btb["bt"]), self._put(bufs["pos"]),
                                 self._put(bufs["tl"]))

            def launch():
                acc_d, tok_d, (kp, vp) = self._fused_verify(
                    self.params, feed_d, self.kv.k_pages, self.kv.v_pages,
                    bt_d, pos_d, tl_d, cross, draft_d, dlen_d,
                    kv, tags_d, qargs, method=self.method, tagged=tagged,
                )
                self.kv.k_pages, self.kv.v_pages = kp, vp
                return acc_d, tok_d

            return bufs, (nb, K, n_max, q_kind, C, tagged), launch
        slots_d, pos_d = self._put(bufs["slots"]), self._put(bufs["pos"])

        def launch():
            acc_d, tok_d, self.cache = self._fused_verify(
                self.params, self.cache, slots_d, feed_d, pos_d, draft_d,
                dlen_d, kv, tags_d, qargs, method=self.method, tagged=tagged,
            )
            return acc_d, tok_d

        return bufs, (nb, K, q_kind, C, tagged), launch

    def _commit_verify(self, items, bufs, acc, tok, t_verify: float) -> list:
        """Advance ``fed``/``last_token`` for all rows at once, then each
        row's tokens, length pointer and tail pages."""
        n = len(items)
        sl = bufs["slots"][:n].astype(np.int64)
        accs = np.asarray(acc[:n], np.int64)
        toks = np.asarray(tok[:n], np.int64)
        self.fed[sl] += accs + 1
        self.last_token[sl] = toks

        results = []
        for i, it in enumerate(items):
            L = int(accs[i])
            if self.paged:
                # the accepted prefix (+ re-fed last token) now has live KV;
                # rejected tail K/V is dead — roll back the length pointer
                # and release any now-unreachable tail pages
                self.tokens[it.slot].extend(
                    int(t) for t in bufs["feed"][i, : L + 1]
                )
                self.kv.set_len(it.slot, int(self.fed[it.slot]))
                self.kv.trim_seq(it.slot)
            results.append(
                VerifyOutcome(
                    slot=it.slot,
                    accept_len=L,
                    token=int(toks[i]),
                    emitted=L + 1,
                    t_verify=t_verify,
                )
            )
        self.stats["batches"] += 1
        self.stats["tokens_verified"] += int(bufs["dlen"][:n].sum())
        self.stats["tokens_committed"] += int(accs.sum()) + n
        self._count_moe(int(bufs["tl"][:n].sum()))
        return results
