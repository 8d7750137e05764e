"""Chunked prompt prefill (DESIGN.md §8): chunked-vs-monolithic
equivalence (first token, prefix-index state; dense and paged backends),
resumable OutOfPages, mixed verify+prefill engine steps, the server's
chunked dispatch flow, and stream invariance across cluster prefill modes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.estimator import EstimatorCoeffs
from repro.models import build
from repro.serving.engine import (
    PrefillChunkItem,
    VerificationEngine,
    VerifyItem,
)
from repro.serving.kv_cache import OutOfPages
from repro.serving.server import WISPServer

COEFFS = EstimatorCoeffs(a=1e-4, b_compute=1e-8, b_read=1e-6, c=1e-3)


@pytest.fixture(scope="module")
def dense_model():
    cfg = get_config("qwen2-7b").reduced()
    bundle = build(cfg)
    params = bundle.init(jax.random.PRNGKey(0), dtype=jnp.float32)
    return cfg, bundle, params


def _engine(cfg, params, *, paged, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_len", 128)
    if paged:
        kw.setdefault("page_size", 4)
    return VerificationEngine(cfg, params, method="greedy", paged=paged, **kw)


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_chunked_prefill_commits_identical_first_token(dense_model, paged):
    """Chunked prefill must commit the byte-identical first token — and,
    paged, the identical prefix-index state — as monolithic prefill, and
    later verification must be indistinguishable between the two."""
    cfg, _, params = dense_model
    prompt = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8]

    mono = _engine(cfg, params, paged=paged)
    chunked = _engine(cfg, params, paged=paged)
    slot_m, first_m = mono.new_session(prompt)
    st = chunked.begin_prefill(prompt)
    while not st.finished:
        chunked.prefill_chunk(st, 4)            # page-aligned chunks
    assert st.first_token == first_m
    assert st.chunks == 3
    assert int(chunked.fed[st.slot]) == int(mono.fed[slot_m]) == len(prompt)
    if paged:
        # identical prefix-index state: same chained page hashes published
        assert (chunked.kv.allocator.prefix_index.keys()
                == mono.kv.allocator.prefix_index.keys())
        assert chunked.tokens[st.slot] == mono.tokens[slot_m] == list(prompt)

    # a verify round after chunked prefill matches the monolithic engine
    d = np.asarray([7, 8, 9], np.int32)
    q = np.zeros((3, cfg.vocab), np.float32)
    (om,) = mono.verify([VerifyItem(slot=slot_m, draft_tokens=d, q_logits=q)])
    (oc,) = chunked.verify([VerifyItem(slot=st.slot, draft_tokens=d,
                                       q_logits=q)])
    assert (om.accept_len, om.token) == (oc.accept_len, oc.token)


def test_chunked_prefill_uses_prefix_cache(dense_model):
    """A chunked prefill of a prompt whose prefix is cached starts past
    the cached pages and still completes with the sharing semantics of the
    monolithic path (same first token, shared physical pages)."""
    cfg, _, params = dense_model
    eng = _engine(cfg, params, paged=True, max_slots=3, max_len=64)
    prompt = [5, 4, 3, 2, 1, 0, 1, 2, 3, 4]                 # 2 full pages
    s1, f1 = eng.new_session(prompt)
    st = eng.begin_prefill(prompt)
    assert st.done == 8 and st.n_cached == 8                # prefix hit
    while not st.finished:
        eng.prefill_chunk(st, 4)
    assert st.first_token == f1
    p1, p2 = eng.kv.tables[s1].pages, eng.kv.tables[st.slot].pages
    assert p1[:2] == p2[:2]                                 # physical sharing
    assert eng.stats["prefix_cached_tokens"] == 8


def test_prefill_chunk_out_of_pages_is_resumable(dense_model):
    """A chunk the pool cannot cover raises with the state intact; after
    pages free the same state resumes and commits the same first token a
    fresh monolithic engine produces."""
    cfg, _, params = dense_model
    eng = VerificationEngine(cfg, params, max_slots=2, max_len=24,
                             method="greedy", paged=True, page_size=4,
                             n_pages=6)                     # 5 usable pages
    blocker, _ = eng.new_session(list(range(40, 52)))       # 3 pages
    st = eng.begin_prefill(list(range(2, 14)))              # needs 3 pages
    eng.prefill_chunk(st, 4)
    eng.prefill_chunk(st, 4)                                # pool now full
    done_before = st.done
    with pytest.raises(OutOfPages):
        eng.prefill_chunk(st, 4)
    assert st.done == done_before and not st.finished       # state intact
    eng.close_session(blocker)                              # frees pages
    eng.prefill_chunk(st, 4)
    assert st.finished
    ref = _engine(cfg, params, paged=True)
    _, want = ref.new_session(list(range(2, 14)))
    assert st.first_token == want


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_engine_step_executes_mixed_batches(dense_model, paged):
    """One engine step with a verify item AND a prefill chunk: outcomes
    align with items, the verify outcome matches a verify-only dispatch,
    and the chunk advances exactly its budget."""
    cfg, _, params = dense_model
    prompt = [3, 1, 4, 1, 5, 9]
    d = np.asarray([7, 8, 9], np.int32)
    q = np.zeros((3, cfg.vocab), np.float32)

    solo = _engine(cfg, params, paged=paged)
    slot_s, _ = solo.new_session(prompt)
    (want,) = solo.verify([VerifyItem(slot=slot_s, draft_tokens=d,
                                      q_logits=q)])

    eng = _engine(cfg, params, paged=paged, max_slots=3)
    slot_v, _ = eng.new_session(prompt)
    st = eng.begin_prefill([9, 8, 7, 6, 5, 4, 3, 2])
    out = eng.step([
        VerifyItem(slot=slot_v, draft_tokens=d, q_logits=q),
        PrefillChunkItem(st, 4),
    ])
    assert (out[0].accept_len, out[0].token) == (want.accept_len, want.token)
    assert out[1].processed == 4 and out[1].first_token is None
    (fin,) = eng.step([PrefillChunkItem(st, 4)])
    assert fin.first_token is not None and fin.done == fin.total == 8


def test_server_chunked_flow_matches_monolithic_first_token(dense_model):
    """Server in chunked mode: open_session returns a ``prefilling``
    handle, dispatch epochs drive the chunks under Algorithm 1, the
    FIRST_TOKEN event (and the deprecated pop_admissions shim) surfaces
    the same first token the monolithic server returns, and the TTFT
    record lands on the event stream against the class's deadline."""
    cfg, _, params = dense_model
    prompt = list(range(2, 22))
    mono = WISPServer(_engine(cfg, params, paged=True), COEFFS)
    first_mono = mono.open_session(0, prompt, slo_class=2).first_token

    srv = WISPServer(_engine(cfg, params, paged=True), COEFFS,
                     prefill="chunked", prefill_chunk_tokens=8)
    vt = lambda served: srv.scheduler.batch_time(served)
    h = srv.open_session(0, prompt, slo_class=2, now=0.0)
    assert h.state == "prefilling" and h.first_token is None
    assert 0 in srv.prefilling and srv.queue_depth == 1
    t, epochs = 0.0, 0
    while 0 in srv.prefilling:
        srv.step(t, verify_time=vt)
        t += 0.01
        epochs += 1
        assert epochs < 10, "chunked prefill did not converge"
    assert h.state == "active" and h.first_token == first_mono
    evs = srv.pop_events()
    firsts = [(e.session_id, e.token) for e in evs
              if e.kind == "FIRST_TOKEN"]
    assert firsts == [(0, first_mono)]
    with pytest.warns(DeprecationWarning):
        assert srv.pop_admissions() == firsts      # legacy shim agrees
    (rec,) = [e.record for e in evs if e.kind == "TTFT_RECORD"]
    assert srv.prefill_log == [rec]                # legacy side-car agrees
    assert rec.chunks == 3 and rec.prompt_len == 20
    assert not rec.violated and rec.ttft > 0.0

    # the activated session verifies normally
    d = np.asarray([1, 2, 3], np.int32)
    q = np.zeros((3, cfg.vocab), np.float32)
    mono.submit(0, d, q, now=t, t_draft=0.0, t_network=0.0)
    srv.submit(0, d, q, now=t, t_draft=0.0, t_network=0.0)
    (vm,) = mono.step(t)
    (vc,) = srv.step(t, verify_time=vt)
    assert (vm.accept_len, vm.token) == (vc.accept_len, vc.token)


def test_server_close_cancels_prefilling_session(dense_model):
    """close_session mid-prefill must retire the slot, the queued chunk,
    and the prefilling record — and must not publish the partial prompt."""
    cfg, _, params = dense_model
    srv = WISPServer(_engine(cfg, params, paged=True), COEFFS,
                     prefill="chunked", prefill_chunk_tokens=8)
    assert srv.open_session(0, list(range(2, 22)), slo_class=3,
                            now=0.0).state == "prefilling"
    srv.step(0.0)                           # one chunk runs
    srv.close_session(0)
    assert 0 not in srv.prefilling
    assert all(r.session_id != 0 for r in srv.pending)
    assert not srv.engine.kv.tables          # pages released
    assert not srv.engine.kv.allocator.prefix_index  # nothing published
    assert len(srv.engine.free_slots) == srv.engine.max_slots


def test_mutually_blocked_prefills_preempt_instead_of_livelock(dense_model):
    """Two long prompts that each fit alone but not together: their
    partial prefills exhaust the pool and every chunk comes back oom.
    The server must preempt the younger session back to the admission
    queue (pages released) so the older completes — not requeue both
    forever."""
    cfg, _, params = dense_model
    # 4 usable pages of 4 tokens; two 12-token prompts need 3 pages each
    eng = VerificationEngine(cfg, params, max_slots=2, max_len=16,
                             method="greedy", paged=True, page_size=4,
                             n_pages=5)
    srv = WISPServer(eng, COEFFS, prefill="chunked", prefill_chunk_tokens=4)
    vt = lambda served: srv.scheduler.batch_time(served)
    assert srv.open_session(0, list(range(2, 14)), slo_class=3,
                            now=0.0).state == "prefilling"
    h1 = srv.open_session(1, list(range(20, 32)), slo_class=3, now=0.1)
    assert h1.state == "prefilling"
    t, epochs = 0.2, 0
    while 0 not in srv.sessions:
        srv.step(t, verify_time=vt)
        t += 0.01
        epochs += 1
        assert epochs < 20, "older prefill starved: admission livelock"
    # the younger session was preempted back to the admission queue (it
    # may already be re-prefilling on the freed slot, but it is not done)
    assert srv.prefill_preemptions >= 1
    assert 1 not in srv.sessions
    evs = srv.pop_events()
    assert [e.session_id for e in evs if e.kind == "PREEMPTED"] == [1]
    assert [e.session_id for e in evs if e.kind == "FIRST_TOKEN"] == [0]
    srv.close_session(0)
    epochs = 0
    while 1 not in srv.sessions:
        srv.step(t, verify_time=vt)
        t += 0.01
        epochs += 1
        assert epochs < 20, "preempted session never re-admitted"
    want = _engine(cfg, params, paged=True).new_session(
        list(range(20, 32)))[1]
    assert h1.first_token == want
    firsts = {e.session_id: e.token for e in srv.pop_events()
              if e.kind == "FIRST_TOKEN"}
    assert firsts[1] == want


def test_paged_prefill_chunks_read_their_own_staging(dense_model):
    """Back-to-back batched chunks commit the monolithic first tokens on
    every run.  A chunk's call returns before the device runs it and the
    next chunk rewrites the pooled host buffers; when a CPU device array
    aliased its numpy source, the first tokens varied from run to run."""
    cfg, _, params = dense_model
    rng = np.random.default_rng(0)
    prompts = [rng.integers(2, cfg.vocab, size=12).tolist() for _ in range(2)]
    # one page holds each prompt, so every chunk reuses one pooled buffer set
    kw = dict(paged=True, page_size=256, max_len=512)
    mono = _engine(cfg, params, **kw)
    want = [mono.new_session(p)[1] for p in prompts]
    for _ in range(4):
        eng = _engine(cfg, params, **kw)
        states = [eng.begin_prefill(p) for p in prompts]
        while not all(st.finished for st in states):
            eng.step([PrefillChunkItem(st, 4) for st in states])
        assert [st.first_token for st in states] == want


def test_cluster_streams_invariant_to_prefill_mode(dense_model):
    """Fixed-work cluster runs under zero / monolithic / chunked prefill
    commit byte-identical streams (timing never reaches a sampling key);
    monolithic and chunked charge a nonzero TTFT, zero does not."""
    from repro.launch.serve import run_serving

    cfg, _, _ = dense_model
    slow = EstimatorCoeffs(a=2e-3, b_compute=1e-7, b_read=1e-6, c=1e-3)
    runs = {}
    for mode in ("zero", "monolithic", "chunked"):
        runs[mode] = run_serving(
            devices=2, rounds=2, k_max=3, verbose=False, seed=0,
            prompt_len=12, prefill_mode=mode, prefill_chunk_tokens=4,
            coeffs=slow,
        )
    streams = {
        mode: [list(d.session.committed) for d in r["result"].devices]
        for mode, r in runs.items()
    }
    assert streams["zero"] == streams["monolithic"] == streams["chunked"]
    ttft = {mode: [s.ttft for s in r["metrics"].sessions]
            for mode, r in runs.items()}
    assert all(v == 0.0 for v in ttft["zero"])
    assert all(v > 0.0 for v in ttft["monolithic"])
    assert all(v > 0.0 for v in ttft["chunked"])
    # the chunked server really chunked: 12-token prompts / 4-token chunks
    assert runs["chunked"]["server"].engine.stats["prefill_chunks"] \
        >= 2 * 3


def test_prefix_cache_stats_reports_backend(dense_model):
    """The dense backend has no prefix cache: its zeros are structural,
    and the backend field is how callers tell that apart from a measured
    0% hit rate (the paged backend reports real counters)."""
    cfg, _, params = dense_model
    dense = _engine(cfg, params, paged=False)
    paged = _engine(cfg, params, paged=True)
    assert dense.prefix_cache_stats()["backend"] == "dense"
    assert dense.stats["backend"] == "dense"
    st = paged.prefix_cache_stats()
    assert st["backend"] == "paged" and paged.stats["backend"] == "paged"
    paged.new_session([1, 2, 3, 4, 5])
    assert paged.prefix_cache_stats()["misses"] >= 1
    dense.new_session([1, 2, 3, 4, 5])
    assert dense.prefix_cache_stats()["hits"] == 0   # structurally zero
