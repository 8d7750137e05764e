"""Verifier fleet (repro.fleet): prefix-locality routing, owner-gated
verdict delivery, lossless migration, and the chaos guarantee — kill a
verifier mid-stream and every committed stream stays byte-identical to
the single-verifier golden run (DESIGN.md §10)."""
import types

import jax
import pytest

from repro.cluster import ClusterConfig, ClusterRuntime, build_fleet
from repro.configs import get_config
from repro.core.estimator import EstimatorCoeffs
from repro.fleet import (
    FleetCapacityError,
    FleetRouter,
    FleetRuntime,
    build_verifier_fleet,
)
from repro.models import build
from repro.serving.client import EdgeDevice
from repro.serving.engine import VerificationEngine
from repro.serving.kv_cache import OutOfPages
from repro.serving.server import WISPServer
from repro.serving.transport import NetworkModel

COEFFS = EstimatorCoeffs(a=1e-4, b_compute=1e-8, b_read=1e-6, c=1e-3)


@pytest.fixture(scope="module")
def dense_pair():
    cfg = get_config("qwen2-7b").reduced()
    bundle = build(cfg)
    tparams = bundle.init(jax.random.PRNGKey(0))
    dparams = bundle.init(jax.random.PRNGKey(1))
    return cfg, tparams, dparams


def _mini_router(cfg, tparams, n=2, page_size=4, max_slots=4):
    """Tiny fleet with small pages so short prompts fill whole pages
    (prefix-index entries) — routing probes have something to hit."""
    verifiers = {}
    for i in range(n):
        eng = VerificationEngine(cfg, tparams, max_slots=max_slots,
                                 max_len=64, page_size=page_size)
        verifiers[f"v{i}"] = WISPServer(eng, COEFFS, network=NetworkModel())
    return FleetRouter(verifiers)


# -- routing -----------------------------------------------------------------

def test_route_least_loaded_fallback(dense_pair):
    cfg, tparams, _ = dense_pair
    router = _mini_router(cfg, tparams)
    a = router.open_session(0, [5, 6, 7, 8], now=0.0)
    b = router.open_session(1, [9, 10, 11, 12], now=0.0)
    assert {a, b} == {"v0", "v1"}        # no coverage: spread by load
    assert router.owner == {0: a, 1: b}


def test_route_prefers_prefix_locality_over_load(dense_pair):
    cfg, tparams, _ = dense_pair
    router = _mini_router(cfg, tparams)
    warm = [3, 4, 5, 6, 7, 8, 9, 10]      # two full 4-token pages
    host = router.open_session(0, warm, now=0.0)
    router.close_session(0, now=0.0)      # publishes the prefix pages
    other = "v1" if host == "v0" else "v0"
    # load the warm verifier heavier than the cold one...
    for sid in (101, 102):
        router.owner[sid] = host
        router.verifiers[host].open_session(
            sid, [30 + sid, 31 + sid], queue_on_full=True, now=0.0)
    assert router._load(host) > router._load(other)
    # ...and locality still wins over least-loaded
    assert router.route(warm) == host
    # a cold prompt falls back to the less loaded verifier
    assert router.route([40, 41, 42, 43]) == other


def test_routing_probe_is_read_only(dense_pair):
    cfg, tparams, _ = dense_pair
    router = _mini_router(cfg, tparams)
    warm = [3, 4, 5, 6, 7, 8, 9, 10]
    host = router.open_session(0, warm, now=0.0)
    router.close_session(0, now=0.0)
    alloc = router.verifiers[host].engine.kv.allocator
    hits0, refs0 = alloc.hits, alloc.refcount.copy()
    for _ in range(3):
        assert router.route(warm) == host
    assert alloc.hits == hits0            # probe never counted as a hit
    assert (alloc.refcount == refs0).all()  # ...and never retained a page


# -- owner-gated, idempotent verdict delivery --------------------------------

def test_deliver_verdict_owner_and_dedup_gates(dense_pair):
    cfg, tparams, _ = dense_pair
    router = _mini_router(cfg, tparams)
    vid = router.open_session(0, [5, 6, 7, 8], now=0.0)
    other = "v1" if vid == "v0" else "v0"
    router.dispatcher.track((0, 0), vid, eta=0.01, now=0.0)
    v = types.SimpleNamespace(session_id=0, round_index=0)
    assert not router.deliver_verdict(other, v)   # not the owner
    assert router.deliver_verdict(vid, v)         # first wins
    # an owner-sent duplicate of a committed round is a REPLAY (lost-ack
    # recovery, DESIGN.md §14): forwarded to the device's round gate,
    # counted separately from the non-owner drop
    assert router.deliver_verdict(vid, v)
    assert router.stats["dropped_verdicts"] == 1
    assert router.stats["replayed_verdicts"] == 1


# -- lossless restore --------------------------------------------------------

def test_restore_session_rebuilds_engine_state(dense_pair):
    cfg, tparams, _ = dense_pair
    eng = VerificationEngine(cfg, tparams, max_slots=2, max_len=64)
    srv = WISPServer(eng, COEFFS, network=NetworkModel())
    committed = [5, 6, 7, 8, 11, 12, 13]
    replayed = srv.restore_session(3, committed, rounds=2)
    s = srv.sessions[3]
    assert replayed == len(committed) - 1
    assert s.committed_len == len(committed)
    assert s.rounds == 2                  # (sid, round) keying resumes here
    # the engine slot invariant the verify hot path depends on:
    # fed = committed_len - 1, last_token = the final committed token
    assert int(eng.fed[s.slot]) == len(committed) - 1
    assert int(eng.last_token[s.slot]) == committed[-1]
    with pytest.raises(ValueError):
        srv.restore_session(3, committed)          # already live
    with pytest.raises(ValueError):
        srv.restore_session(4, [9])                # nothing to replay


def test_migrate_session_moves_ownership(dense_pair):
    cfg, tparams, _ = dense_pair
    router = _mini_router(cfg, tparams)
    sid = 0
    src = router.open_session(sid, [5, 6, 7, 8], now=0.0)
    committed = [5, 6, 7, 8, 21, 22, 23]
    dst, replayed = router.migrate_session(sid, committed, rounds=1, now=0.1)
    assert dst != src and router.owner[sid] == dst
    assert replayed == len(committed) - 1
    assert sid in router.verifiers[dst].sessions
    assert sid not in router.verifiers[src].sessions
    migrated = [ev for _, ev in router.pop_events() if ev.kind == "MIGRATED"]
    assert len(migrated) == 1 and migrated[0].src == src \
        and migrated[0].dst == dst


def test_restore_session_preserves_spec_context(dense_pair):
    """A restored session must resume with the dead owner's adaptive-
    speculation context (DESIGN.md §11), not cold-start defaults: alpha
    feeds Algorithm 1's accept-length forecast and spec_k is the edge
    controller's last draft-length cap."""
    cfg, tparams, _ = dense_pair
    eng = VerificationEngine(cfg, tparams, max_slots=2, max_len=64)
    srv = WISPServer(eng, COEFFS, network=NetworkModel())
    srv.restore_session(3, [5, 6, 7, 8, 11], rounds=2, alpha=0.85, spec_k=3)
    s = srv.sessions[3]
    assert s.alpha == pytest.approx(0.85)
    assert s.spec_k == 3
    # defaults stay the legacy cold-start values for old callers
    srv2 = WISPServer(
        VerificationEngine(cfg, tparams, max_slots=2, max_len=64),
        COEFFS, network=NetworkModel())
    srv2.restore_session(3, [5, 6, 7, 8, 11], rounds=2)
    assert srv2.sessions[3].alpha == pytest.approx(0.6)
    assert srv2.sessions[3].spec_k == 0


def test_migration_carries_adaptive_spec_context(dense_pair):
    """The router's soft-state replica of (alpha, spec_k) refreshes on
    every submit while the owner is alive, so migrating a session off a
    dead verifier restores the context as of the LAST submitted round —
    not the 0.6/0 cold-start a fresh session would get."""
    import numpy as np

    cfg, tparams, _ = dense_pair
    router = _mini_router(cfg, tparams)
    sid, now = 0, 0.0
    src = router.open_session(sid, [5, 6, 7, 8], now=now)
    stream = [ev.token for _, ev in router.pop_events()
              if ev.kind == "FIRST_TOKEN"]
    g = np.random.default_rng(0)

    def one_round(k):
        nonlocal now
        toks = g.integers(0, cfg.vocab, size=k).astype(np.int32)
        qlog = (g.normal(size=(k, cfg.vocab)) * 1.5).astype(np.float32)
        router.submit(sid, toks, qlog, now=now, t_draft=0.01,
                      t_network=0.005)
        while router.queue_depth(src):
            for v in router.step(src, now):
                stream.extend(int(t) for t in toks[: v.accept_len])
                stream.append(int(v.token))
            now += 0.005
        router.pop_events()

    one_round(3)
    s_src = router.verifiers[src].sessions[sid]
    alpha_snap = s_src.alpha              # post-round-1 EWMA estimate
    one_round(2)                          # submit refreshes the replica
    committed = [5, 6, 7, 8] + stream
    dst, _ = router.migrate_session(sid, committed, rounds=2, now=now)
    s_dst = router.verifiers[dst].sessions[sid]
    # the replica was snapshotted at the round-2 submit: alpha as of the
    # round-1 verdict, spec_k = the round-2 draft-length cap
    assert s_dst.alpha == pytest.approx(alpha_snap)
    assert s_dst.alpha != pytest.approx(0.6)
    assert s_dst.spec_k == 2


# -- chaos: kill a verifier mid-stream ---------------------------------------

CHAOS_CCFG = dict(devices=4, rounds=3, k_max=4, max_len=256, seed=0,
                  prefill_mode="chunked", prefill_chunk_tokens=16)


def _edges(cfg, dparams, ccfg, fleet):
    return [
        EdgeDevice(cfg, dparams, k_max=ccfg.k_max, max_len=ccfg.max_len,
                   seed=100 + sp.idx, draft_speed=sp.draft_speed)
        for sp in fleet
    ]


def _golden_run(cfg, tparams, dparams):
    """Single-verifier reference: streams are policy-invariant, so one
    golden run serves every chaos variant."""
    ccfg = ClusterConfig(**CHAOS_CCFG)
    engine = VerificationEngine(cfg, tparams, max_slots=ccfg.devices,
                                max_len=ccfg.max_len)
    server = WISPServer(engine, COEFFS, network=NetworkModel(),
                        prefill="chunked",
                        prefill_chunk_tokens=ccfg.prefill_chunk_tokens)
    fleet = build_fleet(ccfg, cfg.vocab)
    edges = _edges(cfg, dparams, ccfg, fleet)
    ClusterRuntime(server, edges, fleet, ccfg, vocab=cfg.vocab).run()
    return [list(d.response_tokens) for d in edges]


def _fleet_run(cfg, tparams, dparams, *, policy, schedule=None, verifiers=2,
               devices=None, **extra):
    """Chaos variants declare verifier faults through the unified seeded
    `FaultSchedule` DSL (``kill=IDX@T0[+DUR]`` / ``straggle=...``);
    legacy ``fail_at``/``straggle`` tuples ride through ``extra`` to pin
    the deprecation shim."""
    ccfg = ClusterConfig(**CHAOS_CCFG, verifiers=verifiers,
                         fault_schedule=schedule, **extra)
    router = build_verifier_fleet(
        cfg, tparams, ccfg.verifiers, COEFFS, max_slots=ccfg.devices,
        max_len=ccfg.max_len, policy=policy, network=NetworkModel(),
        prefill="chunked", prefill_chunk_tokens=ccfg.prefill_chunk_tokens,
        heartbeat_timeout=ccfg.heartbeat_timeout,
        hedge_factor=ccfg.hedge_factor, hedge_guard=ccfg.hedge_guard,
        devices=devices,
    )
    fleet = build_fleet(ccfg, cfg.vocab)
    edges = _edges(cfg, dparams, ccfg, fleet)
    result = FleetRuntime(router, edges, fleet, ccfg, vocab=cfg.vocab).run()
    return [list(d.response_tokens) for d in edges], router, result


@pytest.fixture(scope="module")
def golden_streams(dense_pair):
    cfg, tparams, dparams = dense_pair
    return _golden_run(cfg, tparams, dparams)


@pytest.mark.parametrize("policy", ["wisp", "fcfs"])
def test_chaos_kill_one_verifier_streams_unchanged(dense_pair, golden_streams,
                                                   policy):
    """Kill one of three verifiers mid-run (the acceptance scenario):
    every admitted session completes (migrated ones included) and every
    stream — the failure-touched ones too — is byte-identical to the
    single-verifier golden run."""
    cfg, tparams, dparams = dense_pair
    streams, router, result = _fleet_run(
        cfg, tparams, dparams, policy=policy, schedule="kill=0@0.15",
        verifiers=3,
    )
    assert router.stats["verifier_downs"] == 1
    assert router.stats["migrations"] + router.stats["reopens"] >= 1
    assert all(len(s) > 0 for s in streams)          # everyone finished
    assert streams == golden_streams                 # byte-identical
    assert len(result.metrics.sessions) == CHAOS_CCFG["devices"]
    assert router.dispatcher.degraded is False       # survivor still serves


def test_chaos_fleet_without_failures_matches_golden(dense_pair,
                                                     golden_streams):
    cfg, tparams, dparams = dense_pair
    streams, router, _ = _fleet_run(cfg, tparams, dparams, policy="wisp")
    assert router.stats["verifier_downs"] == 0
    assert streams == golden_streams


def test_fleet_on_explicit_devices_matches_golden(dense_pair, golden_streams):
    """Replicas placed on explicit devices (each with its own params copy,
    page pools, staging and rng keys there) commit the streams of the
    shared-default-device fleet."""
    cfg, tparams, dparams = dense_pair
    dev = jax.devices()[0]
    streams, router, _ = _fleet_run(cfg, tparams, dparams, policy="wisp",
                                    devices=[dev, dev])
    assert streams == golden_streams
    for srv in router.verifiers.values():
        eng = srv.engine
        assert eng.device == dev
        assert eng.kv.k_pages.devices() == {dev}
        assert eng.rng.devices() == {dev}


@pytest.mark.parametrize("devices", [None, "first"])
def test_fleet_replicas_cycle_over_devices(dense_pair, devices):
    """Replica i lands on ``devices[i % len(devices)]`` (default: every
    device JAX sees); replicas on the params' own device share their
    buffers instead of copying them."""
    cfg, tparams, _ = dense_pair
    given = jax.devices()[:1] if devices == "first" else None
    router = build_verifier_fleet(cfg, tparams, 3, COEFFS, max_slots=2,
                                  max_len=64, devices=given)
    pool = given or jax.devices()
    src = jax.tree.leaves(tparams)
    for i, srv in enumerate(router.verifiers.values()):
        eng = srv.engine
        assert eng.device == pool[i % len(pool)]
        assert eng.kv.k_pages.devices() == {eng.device}
        if eng.device in src[0].devices():
            assert [a.unsafe_buffer_pointer()
                    for a in jax.tree.leaves(eng.params)] == [
                a.unsafe_buffer_pointer() for a in src]


@pytest.mark.parametrize("fault,raised,tried", [
    (OutOfPages("pool full"), FleetCapacityError, 2),
    (RuntimeError("RESOURCE_EXHAUSTED: out of device memory"), RuntimeError, 1),
])
def test_migration_tries_next_replica_only_on_capacity(dense_pair,
                                                       monkeypatch, fault,
                                                       raised, tried):
    """A destination out of pages or slots is skipped; a device fault
    (an OOM, a compile error) surfaces instead of reading as "full"."""
    cfg, tparams, _ = dense_pair
    router = _mini_router(cfg, tparams, n=3)
    router.open_session(0, [5, 6, 7, 8], now=0.0)
    calls = []

    def restore(*a, **k):
        calls.append(1)
        raise fault

    for srv in router.verifiers.values():
        monkeypatch.setattr(srv, "restore_session", restore)
    with pytest.raises(raised) as info:
        router.migrate_session(0, [5, 6, 7, 8, 21], rounds=1, now=0.1)
    assert info.type is raised
    assert len(calls) == tried          # capacity: both other replicas


def test_chaos_straggler_hedged_away(dense_pair, golden_streams):
    """A wedged-but-alive verifier (400x straggle) blows the hedge ETA:
    its sessions migrate and their in-flight rounds re-dispatch; the
    straggler's late verdicts are dropped at the owner gate.  Streams
    stay byte-identical."""
    cfg, tparams, dparams = dense_pair
    streams, router, _ = _fleet_run(
        cfg, tparams, dparams, policy="wisp",
        schedule="straggle=0@0.05+0.95*400", hedge_factor=2.0,
    )
    assert router.dispatcher.stats["hedged"] >= 1
    assert router.stats["redispatches"] >= 1
    assert router.stats["verifier_downs"] == 0   # alive, just slow
    assert streams == golden_streams


def test_chaos_migrate_session_with_spilled_pages(dense_pair):
    """Chaos x tiering (DESIGN.md §12): migrate a session whose KV pages
    sit in the SOURCE verifier's host spill tier.  The destination's
    ``restore_session`` replays the committed stream as a fresh prefill
    (never touching the source's tier) without deadlocking against its
    own tier hooks, the source teardown releases the spilled refs, and
    the stream continues byte-identical to a run that never spilled."""
    import numpy as np

    cfg, tparams, _ = dense_pair

    def _tiered_router(n=2):
        verifiers = {}
        for i in range(n):
            eng = VerificationEngine(
                cfg, tparams, max_slots=4, max_len=64, page_size=4,
                kv_tier_pages=32, spill_idle_epochs=2,
            )
            verifiers[f"v{i}"] = WISPServer(eng, COEFFS,
                                            network=NetworkModel())
        return FleetRouter(verifiers)

    def run(spill: bool):
        router = _tiered_router()
        sid, now = 0, 0.0
        src = router.open_session(sid, [5, 6, 7, 8], now=now)
        stream = [ev.token for _, ev in router.pop_events()
                  if ev.kind == "FIRST_TOKEN"]
        g = np.random.default_rng(0)

        def one_round(owner, k):
            nonlocal now
            toks = g.integers(0, cfg.vocab, size=k).astype(np.int32)
            qlog = (g.normal(size=(k, cfg.vocab)) * 1.5).astype(np.float32)
            router.submit(sid, toks, qlog, now=now, t_draft=0.01,
                          t_network=0.005)
            while router.queue_depth(owner):
                for v in router.step(owner, now):
                    stream.extend(int(t) for t in toks[: v.accept_len])
                    stream.append(int(v.token))
                now += 0.005
            router.pop_events()

        one_round(src, 3)
        src_eng = router.verifiers[src].engine
        if spill:
            slot = router.verifiers[src].sessions[sid].slot
            assert src_eng.spill_session(slot) > 0
            assert src_eng.kv.spilled_pages(slot) > 0
        committed = [5, 6, 7, 8] + stream
        dst, replayed = router.migrate_session(sid, committed, rounds=1,
                                               now=now)
        assert dst != src and replayed == len(committed) - 1
        if spill:
            # the source teardown left no host entry owned by a live
            # sequence — entries were dropped or orphaned to prefix-only
            assert all(e.owner is None
                       for e in src_eng.kv.tier.entries.values())
        one_round(dst, 2)
        one_round(dst, 3)
        return stream

    assert run(spill=True) == run(spill=False)


def test_chaos_verifier_rejoins(dense_pair, golden_streams):
    """A verifier that dies and recovers re-enters the rotation (rejoin
    hook) without perturbing any stream.  Deliberately uses the legacy
    ``ClusterConfig.fail_at`` tuples (not the DSL) to pin the
    deprecation shim `resolve_fault_schedule` compiles onto the unified
    schedule."""
    cfg, tparams, dparams = dense_pair
    streams, router, _ = _fleet_run(cfg, tparams, dparams, policy="wisp",
                                    fail_at=((0, 0.12, 0.5),))
    assert router.stats["verifier_downs"] == 1
    assert router.stats["rejoins"] == 1
    assert streams == golden_streams
