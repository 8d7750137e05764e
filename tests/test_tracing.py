"""Spans of `WISPServer.step` and the engine (`repro.serving.tracing`).

Served epochs on tiny models (the golden scenario's) give the span tree:
children nest inside their parents and do not overlap, queue intervals
join the step that served them, the engine's outcome times are their
spans' durations, a profiler trace holds every step on its own clock, and
a tracer changes no committed token.
"""
import time

import jax
import pytest

import _golden_scenario as G
from repro.serving.engine import (
    PrefillChunkItem,
    VerificationEngine,
    VerifyItem,
    fused_paged_verify_specs,
)
from repro.serving.server import WISPServer
from repro.serving.tracing import NOOP, Tracer, span, timed

STEP_CHILDREN = {"wisp.admit", "wisp.schedule", "wisp.verify",
                 "wisp.verify.commit", "wisp.prefill", "wisp.apply"}
ENGINE_CHILDREN = {
    "wisp.verify": {"wisp.verify.stage", "wisp.verify.launch",
                    "wisp.verify.wait"},
    "wisp.prefill": {"wisp.prefill.stage", "wisp.prefill.launch",
                     "wisp.prefill.wait"},
}


def _engine(tracer=None):
    cfg, params = G._model_for("qwen2-7b")
    return cfg, VerificationEngine(cfg, params, max_slots=4, max_len=128,
                                   seed=7, paged=True, page_size=4,
                                   tracer=tracer)


def _serve(tracer=None, rounds=2, on_step=None):
    """Chunked prefill of one prompt, then verify rounds while a second
    prompt's chunks share the epochs.  ``on_step(server, verdicts)`` runs
    after every step."""
    cfg, eng = _engine(tracer)
    srv = WISPServer(eng, G.COEFFS, prefill="chunked", prefill_chunk_tokens=4)
    now = 0.0

    def step():
        nonlocal now
        verdicts = srv.step(now)
        now += 0.005
        if on_step is not None:
            on_step(srv, verdicts)

    srv.open_session(0, G.PROMPTS[0], slo_class=2, now=now)
    while not srv.sessions:
        step()
    srv.open_session(1, G.PROMPTS[1], slo_class=2, now=now)
    for rnd in range(rounds):
        for sid in list(srv.sessions):
            toks, qlog = G._draft_for(cfg.vocab, sid, rnd)
            srv.submit(sid, toks, qlog, now=now, t_draft=0.02,
                       t_network=0.01)
        while srv.queue_depth:
            step()
    return srv


@pytest.fixture(scope="module")
def served():
    """Records of a traced run, and per step: (epoch, verdicts, the
    server's epoch time)."""
    tr = Tracer()
    steps = []
    _serve(tr, on_step=lambda srv, v: steps.append(
        (tr.epoch, v, srv.last_verify_time)))
    return tr.records(), steps


def test_tracer_records_nesting_and_ring():
    tr = Tracer(capacity=4)
    with tr.span("a", x=1) as a:
        with tr.span("b") as b:
            b.set(y=2)
        tr.interval("q", 0.5, 1.5, (7, 0), kind="verify")
    recs = {r.name: r for r in tr.records()}
    assert recs["b"].parent == a.id and recs["a"].parent == -1
    assert recs["b"].args == {"y": 2} and recs["a"].args == {"x": 1}
    assert recs["q"].args == {"kind": "verify", "ids": (7, 0)}
    assert (recs["q"].t0, recs["q"].t1, recs["q"].parent) == (0.5, 1.5, -1)
    assert recs["a"].t0 <= recs["b"].t0 <= recs["b"].t1 <= recs["a"].t1
    for i in range(5):
        with tr.span(f"s{i}"):
            pass
    assert [r.name for r in tr.records()] == ["s1", "s2", "s3", "s4"]
    # no tracer: every span is the shared no-op, the timed one still times
    assert span(None, "x", y=1) is NOOP
    with timed(None, "x") as t:
        time.sleep(0.001)
    assert t.dt >= 0.001


def test_span_tree(served):
    recs, _ = served
    byid = {r.id: r for r in recs}
    names = {r.name for r in recs}
    assert names == STEP_CHILDREN | {"wisp.step", "wisp.queue"} | set().union(
        *ENGINE_CHILDREN.values())
    kids: dict = {}
    for r in recs:
        assert r.t0 <= r.t1
        if r.parent == -1:
            assert r.name in ("wisp.step", "wisp.queue")
            continue
        p = byid[r.parent]
        assert p.t0 <= r.t0 and r.t1 <= p.t1, (r, p)
        assert r.epoch == p.epoch
        kids.setdefault(r.parent, []).append(r)
    for pid, rs in kids.items():
        parent = byid[pid].name
        allowed = STEP_CHILDREN if parent == "wisp.step" else \
            ENGINE_CHILDREN[parent]
        assert {r.name for r in rs} <= allowed
        rs.sort(key=lambda r: r.t0)
        for x, y in zip(rs, rs[1:]):
            assert x.t1 <= y.t0, (x, y)
    steps = [r for r in recs if r.name == "wisp.step"]
    assert [r.epoch for r in steps] == list(range(1, len(steps) + 1))
    # an epoch that verified and prefilled at once
    assert any(r.args["verify_rows"] and r.args["prefill_rows"] for r in steps)
    # each queue interval ends where the step that served it starts
    start = {r.t0: r for r in steps}
    queued = [r for r in recs if r.name == "wisp.queue"]
    assert {q.args["kind"] for q in queued} == {"verify", "prefill"}
    for q in queued:
        s = start[q.t1]
        assert q.args["ids"] in s.args["ids"] and q.epoch == s.epoch
        assert q.t0 <= q.t1
    assert sum(len(s.args["ids"]) for s in steps) == len(queued)


def test_outcome_times_are_their_spans(served):
    recs, steps = served
    dur = {}
    for r in recs:
        if r.name in ("wisp.verify", "wisp.prefill"):
            dur[r.epoch, r.name] = r.t1 - r.t0
    verified = 0
    for epoch, verdicts, wall in steps:
        tv = dur.get((epoch, "wisp.verify"), 0.0)
        for v in verdicts:
            assert v.t_verify == tv
            verified += 1
        assert wall == tv + dur.get((epoch, "wisp.prefill"), 0.0)
    assert verified


def test_engine_outcomes_carry_span_durations():
    tr = Tracer()
    cfg, eng = _engine(tr)
    slot, _ = eng.new_session(G.PROMPTS[0])
    st = eng.begin_prefill(G.PROMPTS[1])
    toks, qlog = G._draft_for(cfg.vocab, 0, 0)
    out = eng.step([VerifyItem(slot=slot, draft_tokens=toks, q_logits=qlog,
                               rng_tag=(0, 6)),
                    PrefillChunkItem(st, 4)])
    d = {r.name: r.t1 - r.t0 for r in tr.records()}
    assert out[0].t_verify == d["wisp.verify"]
    assert out[1].t_chunk == d["wisp.prefill"]


@pytest.mark.parametrize("cell", ["paged/wisp/chunked",
                                  "dense/wisp/monolithic",
                                  "recurrent/fcfs/chunked"])
def test_streams_unchanged_by_tracer(cell):
    """The golden scenario's committed streams, byte for byte, with and
    without a tracer (against each other: the stored streams depend on
    the CPU the tests run on)."""
    tr = Tracer()
    got = G.run_scenario(*cell.split("/"), engine_overrides={"tracer": tr})
    assert got == G.run_scenario(*cell.split("/"))
    assert any(r.name == "wisp.verify" for r in tr.records())


def test_program_names():
    """The verify program's module keeps ``jit_fused`` (the name the
    benchmark's ``verify_mfu`` matches); the prefill program has a name
    of its own."""
    cfg, eng = _engine()
    specs = fused_paged_verify_specs(cfg, batch=1, k=2, n_pages=9, n_max=2,
                                     top_c=4, page_size=4)
    verify = eng._fused_verify.lower(*specs, method="residual",
                                     tagged=True).as_text()
    assert "module @jit_fused_verify" in verify
    params, _, pool, _, bt, base, tl = specs[:7]
    feed = jax.ShapeDtypeStruct((1, 16), "int32")
    prefill = eng._prefill_paged.lower(params, feed, pool, pool, bt, base,
                                       tl, None).as_text()
    assert "module @jit_prefill_paged" in prefill
    assert "jit_fused" not in prefill
    for backend in ("dense", "recurrent"):
        name, kw = G.BACKENDS[backend]
        c, p = G._model_for(name)
        other = VerificationEngine(c, p, max_slots=2, max_len=32, **kw)
        assert "fused" not in other._fused_verify.__name__


def test_cold_dispatches_count_first_launches():
    cfg, eng = _engine()
    assert "dispatches" not in eng.stats
    slot, _ = eng.new_session(G.PROMPTS[0])
    # one prefill program, which returns the first token itself
    assert eng.stats["cold_dispatches"] == 1
    assert eng.dispatch_counts["prefill"] == 1
    assert "first_token" not in eng.dispatch_counts
    for rnd in range(3):
        toks, qlog = G._draft_for(cfg.vocab, 0, rnd)
        eng.verify([VerifyItem(slot=slot, draft_tokens=toks, q_logits=qlog)])
    assert eng.dispatch_counts["verify"] == 3
    assert eng.stats["cold_dispatches"] == 2
    toks, qlog = G._draft_for(cfg.vocab, 0, 9)
    eng.verify([VerifyItem(slot=slot, draft_tokens=toks[:1],
                           q_logits=qlog[:1])])      # K bucket 2, not 4
    assert eng.stats["cold_dispatches"] == 3


def test_profiler_holds_each_step(tmp_path):
    """Every recorded ``wisp.step`` is a host event of the profiler trace
    with the same ``epoch``, and the two clocks agree through the
    ``bench.traced`` anchor; a server without a tracer adds none."""
    tr = Tracer()
    _serve(rounds=1)                        # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.traced"):
            anchor = time.perf_counter()
            _serve(rounds=1)
            _serve(tr, rounds=1)
    finally:
        jax.profiler.stop_trace()
    path = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    pd = jax.profiler.ProfileData.from_file(str(path))
    events, pjit = {}, set()
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == "bench.traced":
                    t_anchor = e.start_ns * 1e-9
                elif e.name == "wisp.step":
                    events[int(dict(e.stats)["epoch"])] = e.start_ns * 1e-9
                elif e.name.startswith("PjitFunction("):
                    pjit.add(e.name[len("PjitFunction("):-1])
    steps = [r for r in tr.records() if r.name == "wisp.step"]
    assert sorted(events) == [r.epoch for r in steps]
    for r in steps:
        assert abs((events[r.epoch] - t_anchor) - (r.t0 - anchor)) < 1e-3
    # the served path's programs: one verify module holds "jit_fused"
    assert {"fused_verify", "prefill_paged"} <= pjit
    assert [n for n in pjit if n.startswith("fused")] == ["fused_verify"]
