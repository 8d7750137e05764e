"""One run of one cell: set-up, the measured window, the check.

The window drives the program's own served path -- `WISPServer`
(``open_session``, ``submit``, ``step``, ``pop_events``) over its
`VerificationEngine`, paged KV cache and paged-attention kernel -- on the
wall clock, from an emulated edge fleet that drafts on the host.  Only
this process touches the chip.

Timeline of a run (seconds from ``t0``, the end of set-up): sessions
arrive open loop from 0; ``[preroll, preroll + seconds)`` is the window;
after it the load goes on until every event due in the window has been
answered or ``drain_s`` has passed.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import heapq
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import traffic as TR


@dataclasses.dataclass
class Round:
    due: float                 # upload due at the server
    fed: int                   # committed tokens with KV, as the edge counts
    draft: np.ndarray
    t_step0: float = math.nan  # start of the step that served it
    t_done: float = math.nan   # verdict on the host
    accept_len: int = -1
    token: int = -1


@dataclasses.dataclass
class Session:
    plan: TR.SessionPlan
    prompt: np.ndarray
    rng: np.random.Generator
    first_token: int = -1
    t_first: float = math.nan
    gen: list = dataclasses.field(default_factory=list)
    rounds: list = dataclasses.field(default_factory=list)
    commits: list = dataclasses.field(default_factory=list)  # (t, n)
    t_closed: float = math.nan
    done: bool = False


@dataclasses.dataclass
class Step:
    t0: float
    t1: float
    verify_rows: list          # [(fed, n_tokens)] per verify row
    prefill_rows: list         # [(done, n)] per prefill chunk
    pages_in_use: int


class Window:
    """Everything the metric readers read from one run."""

    def __init__(self, mix, seconds, preroll):
        self.mix = mix
        self.seconds = seconds
        self.w0 = preroll
        self.w1 = preroll + seconds
        self.sessions: dict[int, Session] = {}
        self.steps: list[Step] = []
        self.lateness: list[float] = []
        self.stats0: dict = {}
        self.stats1: dict = {}
        self.n_pages = 0
        self.trace = None          # bench.trace.Reduced, traced runs only
        self.trace_span = None     # (t_start, t_stop) on the run clock
        self.config = None         # the configuration file's sizes
        self.peaks = None          # the device's row of bench/peaks.json
        self.setup_s = math.nan

    def in_window(self, t: float) -> bool:
        return self.w0 <= t < self.w1


def compact_q(k: int, c: int, vocab: int, rng) -> tuple:
    """K draft tokens from the uniform draft distribution q, with the
    program's `CompactQ` wire statistics of it: exact ``logq_tok``, a
    top-C table (C distinct ids, every entry 1/V) and the tail mass."""
    from repro.core.speculative import CompactQ

    draft = rng.integers(0, vocab, k).astype(np.int32)
    logq = np.float32(-np.log(vocab))
    top = np.stack([rng.choice(vocab, c, replace=False) for _ in range(k)])
    q = CompactQ(
        logq_tok=np.full(k, logq, np.float32),
        top_idx=top.astype(np.int32),
        top_logq=np.full((k, c), logq, np.float32),
        tail=np.full(k, 1.0 - c / vocab, np.float32),
    )
    return draft, q


def drive(server, mix: dict, plans: list, prefixes: list, seed: int,
          seconds: float, *, tracer=None) -> Window:
    """The measured window.  ``tracer`` (traced runs) is started and
    stopped between steps inside the window."""
    engine = server.engine
    vocab = engine.cfg.vocab
    K, C = mix["k"], mix["q_top_c"]
    preroll, drain = mix["preroll_s"], mix["drain_s"]
    w = Window(mix, seconds, preroll)
    horizon = preroll + seconds + drain
    heap: list = []
    seq = 0

    def push(t, kind, sid):
        nonlocal seq
        seq += 1
        heapq.heappush(heap, (t, seq, kind, sid))

    for p in plans:
        if p.due < horizon:
            push(p.due, "open", p.sid)
    w.stats0 = dict(engine.stats)
    clock = time.perf_counter
    t0 = clock()
    traced = False
    trace_at = preroll + min(2.0, seconds / 4)
    trace_len = min(4.0, seconds / 4)

    def now():
        return clock() - t0

    #: opens and uploads due in the window that have no answer yet
    open_in_window = 0
    # host spans name the device's idle gaps in traced runs only
    span = jax.profiler.TraceAnnotation if tracer is not None else \
        (lambda name: contextlib.nullcontext())

    while True:
        t = now()
        if t >= horizon or (t >= w.w1 and not open_in_window and all(
                p.due >= w.w1 or p.sid in w.sessions for p in plans)):
            break
        if tracer is not None:
            if not traced and t >= trace_at:
                tracer.start()
                w.trace_span = [t, math.nan]
                traced = True
            elif traced and math.isnan(w.trace_span[1]) and \
                    t >= trace_at + trace_len:
                tracer.stop()
                w.trace_span[1] = now()
        edge = span("bench.edge")
        edge.__enter__()
        while heap and heap[0][0] <= t:
            due, _, kind, sid = heapq.heappop(heap)
            w.lateness.append(t - due)
            open_in_window += w.in_window(due) and kind != "close"
            if kind == "open":
                p = plans[sid]
                prompt = np.concatenate([
                    prefixes[p.prefix],
                    TR.suffix_tokens(seed, sid, p.suffix_len, vocab)])
                s = Session(p, prompt, TR.draft_rng(seed, sid))
                w.sessions[sid] = s
                server.open_session(sid, prompt, slo_class=p.edge["slo_class"],
                                    draft_speed=p.edge["draft_tok_s"], now=due)
            elif kind == "submit":
                s = w.sessions[sid]
                draft, q = compact_q(K, C, vocab, s.rng)
                fed = len(s.prompt) + len(s.gen) - 1
                s.rounds.append(Round(due=due, fed=fed, draft=draft))
                e = s.plan.edge
                server.submit(sid, draft, q_compact=q, now=due,
                              t_draft=K / e["draft_tok_s"],
                              t_network=e["rtt_s"])
            else:
                server.close_session(sid, now=due)
                w.sessions[sid].t_closed = due
        edge.__exit__(None, None, None)
        t = now()
        if not server.queue_depth:
            nxt = heap[0][0] if heap else horizon
            with span("bench.idle"):
                time.sleep(max(0.0, min(nxt, horizon) - t))
            continue
        t_a = now()
        with span("bench.step"):
            server.step(t_a)
        t_b = now()
        rows_v, rows_p = [], []
        for r in server.last_served:
            if r.kind == "verify":
                rows_v.append((r.cached_len, r.draft_len + 1))
            else:
                rows_p.append((r.cached_len, r.prefill_tokens))
        w.steps.append(Step(t_a, t_b, rows_v, rows_p,
                            engine.prefix_cache_stats()["pages_in_use"]))
        for ev in server.pop_events():
            s = w.sessions.get(ev.session_id)
            name = type(ev).__name__
            if name == "FirstToken":
                open_in_window -= w.in_window(s.plan.due)
                s.first_token, s.t_first = int(ev.token), t_b
                s.gen.append(int(ev.token))
                e = s.plan.edge
                push(t_b + e["rtt_s"] + K / e["draft_tok_s"], "submit",
                     ev.session_id)
            elif name == "VerdictEvent":
                v = ev.verdict
                r = s.rounds[-1]
                open_in_window -= w.in_window(r.due)
                r.t_step0, r.t_done = t_a, t_b
                r.accept_len, r.token = int(v.accept_len), int(v.token)
                new = [int(x) for x in r.draft[:v.accept_len]] + [int(v.token)]
                s.gen.extend(new)
                s.commits.append((t_b, len(new)))
                e = s.plan.edge
                if len(s.gen) - 1 >= s.plan.response_len:
                    s.done = True
                    push(t_b + e["rtt_s"] / 2, "close", ev.session_id)
                else:
                    push(t_b + e["rtt_s"] + K / e["draft_tok_s"], "submit",
                         ev.session_id)
    if tracer is not None and traced and math.isnan(w.trace_span[1]):
        tracer.stop()
        w.trace_span[1] = now()
    w.t_end = now()
    w.stats1 = dict(engine.stats)
    return w


# -- set-up ------------------------------------------------------------------
def _bucket(n: int, lo: int = 1) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


def shape_grid(mix: dict, page: int) -> dict:
    """The engine programs the window can reach, by the program's buckets:
    verify batches of 1..max_batch rows (powers of two) by block tables of
    1..max pages, and prefill chunk batches of the same rows by chunk
    lengths (whole ``len_quantum`` multiples up to the chunk size, bucketed
    from 16) by the same block tables."""
    srv = mix["server"]
    nbs = [1 << i for i in range(int(math.log2(
        _bucket(srv["max_batch_requests"]))) + 1)]
    n_pages = -(-mix["max_len"] // page)
    nmax = [1 << i for i in range(int(math.log2(_bucket(n_pages))) + 1)]
    chunk = srv["prefill_chunk_tokens"]
    q = mix["len_quantum"]
    ts = sorted({_bucket(n, 16) for n in range(q, chunk + 1, q)})
    return {"nb": nbs, "n_max": nmax, "T": ts}


def warm_up(engine, mix: dict, seed: int) -> int:
    """Run every program of `shape_grid` once through the engine's public
    calls, on throwaway sessions; returns the number of programs run."""
    from repro.serving.engine import PrefillChunkItem, VerifyItem

    page = engine.page_size
    g = shape_grid(mix, page)
    vocab = engine.cfg.vocab
    rng = np.random.default_rng([seed, 99])
    K, C = mix["k"], mix["q_top_c"]
    top = max(g["n_max"])
    # a long warm prompt whose full pages later prompts reuse from the
    # prefix cache, so a row reaches n pages with one page of new tokens
    long = rng.integers(2, vocab, page * top).astype(np.int32)
    st = engine.begin_prefill(long)
    while not st.finished:
        engine.prefill_chunk(st, mix["server"]["prefill_chunk_tokens"])
    ran = set()
    # a finished chunk's first token is an eager gather + argmax over the
    # finished rows: one small program per (rows, chunk length, finished)
    for nb in g["nb"]:
        for T in g["T"]:
            for m in range(1, nb):
                states = [engine.begin_prefill(rng.integers(
                    2, vocab, T + (page if i >= m else 0)).astype(np.int32))
                    for i in range(nb)]
                engine.step([PrefillChunkItem(s, T) for s in states])
                for s in states:
                    engine.close_session(s.slot)
    # verify rows end inside the row's last page: fed + K + 1 <= pages * P
    t_v = min(g["T"]) if min(g["T"]) + K + 1 <= page else page - K - 1

    def open_rows(nb, n_max, n_new):
        return [engine.begin_prefill(np.concatenate([
            long[:page * (n_max - 1)],
            rng.integers(2, vocab, n_new).astype(np.int32)]))
            for _ in range(nb)]

    for n_max in g["n_max"]:
        for nb in g["nb"]:
            for T in g["T"]:
                states = open_rows(nb, n_max, T)
                engine.step([PrefillChunkItem(s, T) for s in states])
                ran.add(("prefill", nb, T, n_max))
                for s in states:
                    engine.close_session(s.slot)
            states = open_rows(nb, n_max, t_v)
            engine.step([PrefillChunkItem(s, t_v) for s in states])
            items = []
            for s in states:
                d, q = compact_q(K, C, vocab, rng)
                items.append(VerifyItem(
                    slot=s.slot, draft_tokens=d, q_compact=q,
                    rng_tag=(1 << 30, int(engine.fed[s.slot]))))
            engine.verify(items)
            ran.add(("verify", nb, n_max))
            for s in states:
                engine.close_session(s.slot)
    engine.close_session(st.slot)
    return len(ran)


#: device memory left beside the largest program (staging, small arrays)
HBM_SPARE = 256 << 20


def size_pool(cfg, mix: dict, device, page: int) -> tuple[int, dict]:
    """The largest page pool with which the largest verify and prefill
    programs fit the device's ``bytes_limit``, from the compiled programs'
    own memory analysis at two trial pool sizes (need is linear in the
    pool)."""
    from functools import partial

    from repro.models import transformer
    from repro.serving.engine import fused_paged_verify, fused_paged_verify_specs

    if "n_pages" in mix["server"]:          # a fixed pool (CPU tests)
        return mix["server"]["n_pages"], {}
    g = shape_grid(mix, page)
    nb, n_max, T = max(g["nb"]), max(g["n_max"]), max(g["T"])
    sh = jax.sharding.SingleDeviceSharding(device)
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sh)
    fv = fused_paged_verify(partial(transformer.decode_paged, cfg))
    fp = jax.jit(partial(transformer.decode_paged, cfg, dropless=False),
                 donate_argnums=(2, 3))
    need = {}
    for n in (64, 128):
        specs = fused_paged_verify_specs(
            cfg, batch=nb, k=_bucket(mix["k"], 2), n_pages=n, n_max=n_max,
            top_c=mix["q_top_c"], page_size=page, cache_dtype=jnp.bfloat16,
            sharding=sh)
        m1 = fv.lower(*specs, method="residual", tagged=True).compile() \
            .memory_analysis()
        pool = specs[2]
        m2 = fp.lower(specs[0], S((nb, T), jnp.int32), pool, pool,
                      S((nb, n_max), jnp.int32), S((nb,), jnp.int32),
                      S((nb,), jnp.int32), None).compile().memory_analysis()
        need[n] = max(m.argument_size_in_bytes + m.output_size_in_bytes
                      - m.alias_size_in_bytes + m.temp_size_in_bytes
                      for m in (m1, m2))
    per_page = (need[128] - need[64]) / 64
    fixed = need[64] - 64 * per_page
    limit = device.memory_stats()["bytes_limit"]
    n_pages = int((limit - HBM_SPARE - fixed) // per_page)
    return n_pages, {"bytes_limit": limit, "need_fixed": fixed,
                     "need_per_page": per_page, "spare": HBM_SPARE}


def enable_cache(root) -> str:
    import os

    d = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return d


def prefill_prefixes(server, prefixes: list, chunk_tokens: int) -> None:
    """Set-up prefill of the mix's shared prefixes through the server's
    normal path (open, chunked prefill, close), so the prefix cache holds
    them when the window opens, as a deployment's would."""
    for i, p in enumerate(prefixes):
        sid = -1 - i
        server.open_session(sid, p, slo_class=max(server.slo_classes))
        while server.queue_depth:
            server.step(0.0)
        server.pop_events()
        server.close_session(sid)
        server.pop_events()


class CompileCounter:
    """Counts compilations (and persistent-cache loads) between ``start``
    and ``stop``: there should be none inside the window."""

    KEYS = ("backend_compile", "cache_hits", "jaxpr_trace")

    def __init__(self):
        self.n = 0
        self.on = False
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(self._dur)
        monitoring.register_event_listener(self._ev)

    def _dur(self, event, seconds, **_):
        self._ev(event)

    def _ev(self, event, **_):
        if self.on and any(k in event for k in self.KEYS):
            self.n += 1

    def start(self):
        self.on = True

    def stop(self):
        self.on = False


class Tracer:
    """The profiler over part of the window, marked by a ``bench.traced``
    host span on the trace's own clock."""

    def __init__(self, directory):
        self.dir = str(directory)
        self._span = None

    def start(self):
        jax.profiler.start_trace(self.dir)
        self._span = jax.profiler.TraceAnnotation("bench.traced")
        self._span.__enter__()

    def stop(self):
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()


def free() -> None:
    """Drop every array the program left on the device."""
    gc.collect()
    for a in jax.live_arrays():
        a.delete()
    gc.collect()
