"""Serving driver: end-to-end WISP loop (drafting edges + verification
server) on real models.

Two drive modes over the same models, workload and scheduler:

  * **event-driven** (default) — `repro.cluster.ClusterRuntime`: per-device
    virtual clocks, drafting overlapped with in-flight verification
    (speculative continue, commit-or-rollback), server dispatch epochs on
    their own timer, transport delays from NetworkModel.  WDT, queueing and
    per-class violations are *measured* from the interleaved execution.
  * **lock-step** (``sync=True`` / ``--sync``) — the original synchronous
    round loop: every device drafts, every request verifies, repeat.  WDT
    can only be accounted analytically here, but the mode is the reference
    the event-driven stream-equivalence guarantee is checked against.

Both commit byte-identical per-session token streams for the same seed
(position-folded draft keys + per-request verification keys).

Example:
  python -m repro.launch.serve --target qwen2-7b --draft qwen2-7b \\
      --reduced --devices 4 --rounds 8 --policy wisp
  python -m repro.launch.serve --devices 4 --rounds 8 --policy edf
  python -m repro.launch.serve --devices 4 --rounds 8 --sync   # lock-step
  python -m repro.launch.serve --target qwen2-7b-14l --no-reduced \
      --self-draft --devices 4 --rounds 3 --prefill chunked \
      --q-mode compact                                   # one TPU chip
"""
from __future__ import annotations

import argparse
import time
import warnings

import jax

from repro.cluster import ClusterConfig, ClusterRuntime, build_fleet
from repro.cluster.workload import TENANT_MIXES, build_tenant_registry
from repro.common.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.core.estimator import EstimatorCoeffs, analytic_tpu_coeffs
from repro.core.scheduler import available_policies
from repro.core.speculation import available_spec_policies
from repro.core.predictor import RejectionPredictor
from repro.core.wdt import IterationLog, WDTStats
from repro.models import build
from repro.serving.client import EdgeDevice
from repro.serving.engine import VerificationEngine
from repro.serving.server import WISPServer
from repro.serving.transport import NetworkModel


def run_serving(
    target_arch: str = "qwen2-7b",
    draft_arch: str | None = None,
    *,
    reduced: bool = True,
    devices: int = 4,
    rounds: int = 8,
    k_max: int = 6,
    policy: str = "wisp",
    scheduler: str | None = None,       # DEPRECATED alias of ``policy``
    predictor: RejectionPredictor | None = None,
    prompt_len: int = 8,
    max_len: int = 512,
    seed: int = 0,
    verbose: bool = True,
    sync: bool = False,
    speculate: bool = True,
    greedy: bool = False,
    churn: bool = False,
    horizon: float | None = None,
    draft_speeds: tuple = (30.0, 50.0, 80.0),
    spec_policy: str = "static",
    link_rtts: tuple = (),
    coeffs: EstimatorCoeffs | None = None,
    dispatch_interval: float = 0.004,
    slo_speeds: dict | None = None,
    sched_cfg=None,
    self_draft: bool = False,
    method: str = "residual",
    q_mode: str = "dense",
    q_top_c: int = 64,
    prefill_mode: str = "zero",
    prefill_chunk_tokens: int = 32,
    ttft_slo: dict | None = None,
    think_time_mean: float = 0.25,
    response_len_mean: float = 24.0,
    verifiers: int = 1,
    fail_at: tuple = (),
    straggle: tuple = (),
    fault_schedule=None,
    link_timeout: float | None = None,
    link_backoff: float = 2.0,
    link_degrade: bool = False,
    link_jitter: float = 0.0,
    heartbeat_interval: float = 0.05,
    heartbeat_timeout: float = 0.15,
    hedge_factor: float = 8.0,
    hedge_guard: float = 0.01,
    kv_tier_pages: int = 0,
    spill_quantize: bool = False,
    spill_idle_epochs: int = 2,
    tenants=None,
    tenant_mix=None,
    verifier_devices=None,
):
    """Run the WISP serving stack; returns a dict with per-device ``stats``,
    aggregate ``total``, the ``edges`` / ``server`` objects and — in
    event-driven mode — the ``ClusterResult`` under ``"result"``.

    ``policy`` selects the server's batch-selection rule from the
    scheduling-policy registry (``repro.core.scheduler``): ``"wisp"``
    (Algorithm 1; legacy alias ``"slo"``), ``"fcfs"``, ``"edf"``,
    ``"priority"``.  ``spec_policy`` selects each edge device's
    draft-length controller from the speculation registry
    (``repro.core.speculation``): ``"static"`` (fixed K = k_max) or
    ``"adaptive"`` (per-block K from acceptance, RTT and verifier load,
    DESIGN.md §11).  ``link_rtts`` gives devices heterogeneous link base
    RTTs (cycled round-robin, like ``draft_speeds``).

    Edge-link fault domain (DESIGN.md §14): ``fault_schedule`` injects a
    seeded chaos plan (a `repro.chaos.FaultSchedule`, a preset name or a
    DSL string) on every device's uplink/downlink; ``link_timeout``
    arms the edge's per-round retry/backoff loop (idempotent under the
    ``(session_id, round_index)`` key); ``link_degrade`` lets link
    health shrink speculation depth (K=1 while the link is down);
    ``link_jitter`` adds seeded per-message log-normal latency jitter.

    Multi-tenant serving (DESIGN.md §13): ``tenant_mix`` is a named
    workload mix from ``repro.cluster.workload.TENANT_MIXES`` (or an
    explicit tuple of `TenantWorkload`) that splits the device fleet
    into per-tenant groups and compiles their admission contracts into
    a shared `TenantRegistry`; ``tenants`` adds/overrides registry
    entries (`TenantSpec` objects or ``name[:key=value]*`` CLI spec
    strings).  Both empty = the legacy single-tenant stack.

    ``verifier_devices`` (with ``verifiers > 1``) overrides where the
    fleet's replicas live (`build_verifier_fleet`; default: replica i on
    ``jax.devices()[i % n]``) — ``[d] * verifiers`` stacks them all on
    ``d``."""
    if scheduler is not None:
        if policy != "wisp" and policy != scheduler:
            raise ValueError(
                f"pass either policy={policy!r} or the deprecated "
                f"scheduler={scheduler!r}, not both"
            )
        warnings.warn(
            "run_serving(scheduler=...) is deprecated; use policy=...",
            DeprecationWarning, stacklevel=2,
        )
        policy = scheduler
    if q_mode == "none" and method != "greedy":
        # a residual/target verifier with no q statistics would silently
        # fall back to the staging buffers' uniform fill — an accept test
        # of u <= p·V, not the paper's rule.  Only greedy reads no q.
        raise ValueError(
            f"q_mode='none' requires method='greedy' (got {method!r}): "
            "residual/target verification needs dense or compact q"
        )
    tcfg = get_config(target_arch)
    dcfg = get_config(draft_arch or target_arch)
    if reduced:
        tcfg, dcfg = tcfg.reduced(), dcfg.reduced()
    if dcfg.vocab != tcfg.vocab:
        raise ValueError("draft/target vocab mismatch")

    tparams = jax.jit(build(tcfg).init)(jax.random.PRNGKey(seed))
    if self_draft:
        # self-speculation: the draft IS the target (with greedy drafting
        # and greedy verification every block fully accepts and every
        # speculative continuation commits — the overlap-pipelining upper
        # bound)
        dcfg, dparams = tcfg, tparams
    else:
        dparams = jax.jit(build(dcfg).init)(jax.random.PRNGKey(seed + 1))

    if sync and prefill_mode != "zero":
        # the lock-step reference has no clock to charge prefill against;
        # it always opens sessions through the blocking monolithic path
        raise ValueError("--sync supports prefill_mode='zero' only")
    if sync and (fault_schedule is not None or link_timeout is not None
                 or link_jitter):
        # the lock-step loop has no virtual clock to lose messages or arm
        # retry timers against
        raise ValueError("--sync does not support the edge-link fault "
                         "domain (fault_schedule/link_timeout/link_jitter)")
    if isinstance(tenant_mix, str):
        if tenant_mix not in TENANT_MIXES:
            raise ValueError(
                f"unknown tenant mix {tenant_mix!r}; "
                f"known: {sorted(TENANT_MIXES)}"
            )
        tenant_workloads = TENANT_MIXES[tenant_mix]
    else:
        tenant_workloads = tuple(tenant_mix or ())
    if tenant_workloads and sync:
        raise ValueError("--sync is single-tenant only")
    ccfg = ClusterConfig(
        devices=devices,
        rounds=None if churn else rounds,
        horizon=horizon,
        k_max=k_max,
        draft_speeds=tuple(draft_speeds),
        prompt_len=prompt_len,
        max_len=max_len,
        seed=seed,
        speculate=speculate,
        spec_policy=spec_policy,
        link_rtts=tuple(link_rtts),
        dispatch_interval=dispatch_interval,
        prefill_mode=prefill_mode,
        prefill_chunk_tokens=prefill_chunk_tokens,
        think_time_mean=think_time_mean,
        response_len_mean=response_len_mean,
        q_mode=q_mode,
        q_top_c=q_top_c,
        verifiers=verifiers,
        fail_at=tuple(fail_at),
        straggle=tuple(straggle),
        fault_schedule=fault_schedule,
        link_timeout=link_timeout,
        link_backoff=link_backoff,
        link_degrade=link_degrade,
        jitter_sigma=link_jitter,
        heartbeat_interval=heartbeat_interval,
        heartbeat_timeout=heartbeat_timeout,
        hedge_factor=hedge_factor,
        hedge_guard=hedge_guard,
        kv_tier_pages=kv_tier_pages,
        spill_quantize=spill_quantize,
        spill_idle_epochs=spill_idle_epochs,
        tenant_workloads=tenant_workloads,
    )
    fleet = build_fleet(ccfg, tcfg.vocab)
    devices = len(fleet)                 # tenant mixes resize the fleet

    # one registry per run: shared across every verifier so tenant
    # budgets and fair-share accounting are fleet-global
    from repro.tenancy import TenantRegistry, TenantSpec

    if isinstance(tenants, TenantRegistry):
        registry = tenants
    else:
        registry = build_tenant_registry(ccfg)
        for spec in tenants or ():
            if isinstance(spec, str):
                spec = TenantSpec.parse(spec)
            registry.register(spec)

    coeffs = coeffs or analytic_tpu_coeffs(tcfg)
    net = NetworkModel()
    if verifiers > 1:
        if sync:
            raise ValueError("--sync is single-verifier only")
        from repro.fleet import build_verifier_fleet

        router = build_verifier_fleet(
            tcfg, tparams, verifiers, coeffs, max_slots=devices,
            max_len=max_len, method=method, policy=policy,
            sched_cfg=sched_cfg, network=net,
            prefill="chunked" if prefill_mode == "chunked" else "monolithic",
            prefill_chunk_tokens=prefill_chunk_tokens,
            slo_classes=slo_speeds, ttft_slo=ttft_slo,
            heartbeat_timeout=heartbeat_timeout,
            hedge_factor=hedge_factor, hedge_guard=hedge_guard,
            kv_tier_pages=kv_tier_pages, spill_quantize=spill_quantize,
            spill_idle_epochs=spill_idle_epochs,
            tenants=registry, devices=verifier_devices,
        )
        engine = next(iter(router.verifiers.values())).engine
        server = router
    else:
        engine = VerificationEngine(tcfg, tparams, max_slots=devices,
                                    max_len=max_len, method=method,
                                    kv_tier_pages=kv_tier_pages,
                                    spill_quantize=spill_quantize,
                                    spill_idle_epochs=spill_idle_epochs)
        server = WISPServer(
            engine, coeffs, policy=policy, network=net,
            slo_classes=slo_speeds, sched_cfg=sched_cfg,
            prefill="chunked" if prefill_mode == "chunked" else "monolithic",
            prefill_chunk_tokens=prefill_chunk_tokens, ttft_slo=ttft_slo,
            tenants=registry,
        )

    edges = [
        EdgeDevice(
            dcfg, dparams, predictor=predictor, k_max=k_max,
            max_len=max_len, seed=seed + 10 + sp.idx,
            draft_speed=sp.draft_speed, greedy=greedy,
            q_mode=q_mode, q_top_c=q_top_c,
            spec_policy=spec_policy,
            spec_cfg={"degrade": True} if link_degrade else None,
        )
        for sp in fleet
    ]

    if sync:
        return _run_lockstep(server, edges, fleet, rounds, net, verbose)

    t_wall0 = time.time()
    if verifiers > 1:
        from repro.fleet import FleetRuntime

        runtime = FleetRuntime(router, edges, fleet, ccfg, vocab=tcfg.vocab)
    else:
        runtime = ClusterRuntime(server, edges, fleet, ccfg, vocab=tcfg.vocab)
    result = runtime.run()
    wall = time.time() - t_wall0
    engines = server.engines if verifiers > 1 else [engine]
    n_batches = sum(e.stats["batches"] for e in engines)
    n_chunks = sum(e.stats["prefill_chunks"] for e in engines)

    m = result.metrics
    stats = [m.per_session.get(sp.idx, WDTStats()) for sp in fleet] \
        if not churn else []
    total = WDTStats()
    for it in m.iterations:
        total.add(it, 0.0)
    if verbose:
        print(f"[serve] mode=event devices={devices} "
              f"{'horizon=%.1fs' % result.horizon if churn else 'rounds=%d' % rounds} "
              f"policy={server.policy} spec_policy={spec_policy} "
              f"speculate={speculate} prefill={prefill_mode}")
        if prefill_mode != "zero" and m.sessions:
            # chunked mode logs TTFT-deadline outcomes per prefill; the
            # monolithic path has no prefill_log, so judge its sessions'
            # measured TTFT against the same per-class budgets
            ttft_viol = (
                sum(r.violated for r in server.prefill_log)
                if server.prefill_log
                else sum(s.ttft > server.ttft_slo[s.slo_class]
                         for s in m.sessions)
            )
            print(f"[serve] ttft: p50={m.ttft_quantile(0.5)*1e3:.1f} ms "
                  f"p99={m.ttft_quantile(0.99)*1e3:.1f} ms "
                  f"prefill_chunks={n_chunks} "
                  f"ttft_violations={ttft_viol}")
        print(f"[serve] drafted={total.drafted} accepted={total.accepted} "
              f"committed={total.committed} acceptance={total.acceptance_rate:.3f}")
        print(f"[serve] measured: goodput={m.goodput(result.horizon):.1f} tok/s "
              f"wdt={m.t_wdt*1e3:.1f} ms waste_frac={m.waste_fraction():.3f} "
              f"mean_queue={m.mean_queue_time()*1e3:.2f} ms")
        s = m.spec
        print(f"[serve] speculation: commits={s.commits} rollbacks={s.rollbacks} "
              f"salvaged={s.salvaged} discarded={s.discarded} "
              f"commit_rate={s.commit_rate:.2f}")
        print(f"[serve] sessions={len(m.sessions)} "
              f"violations={m.violations()} "
              f"deadline_misses={m.deadline_violations()} "
              f"engine batches={n_batches} wall={wall:.1f}s")
        if tenant_workloads:
            weights = {tw.name: tw.weight for tw in tenant_workloads}
            print(f"[serve] tenants: "
                  f"jain_fairness={m.jain_fairness(result.horizon, weights):.3f}")
            for tn, row in m.per_tenant(result.horizon).items():
                print(f"[serve]   {tn}: "
                      f"goodput={row['goodput_tok_s']:.1f} tok/s "
                      f"sessions={row['sessions']} "
                      f"violations={row['session_violations']} "
                      f"rejections={row['rejections']}")
        if kv_tier_pages > 0:
            sp_pages = sum(e.stats["pages_spilled"] for e in engines)
            pi_pages = sum(e.stats["pages_paged_in"] for e in engines)
            sp_mb = sum(e.stats["spill_bytes"] for e in engines) / 2**20
            pi_mb = sum(e.stats["pagein_bytes"] for e in engines) / 2**20
            print(f"[serve] kv-tier: host_pages={kv_tier_pages} "
                  f"quantize={spill_quantize} spilled={sp_pages} "
                  f"({sp_mb:.2f} MiB) paged_in={pi_pages} "
                  f"({pi_mb:.2f} MiB)")
        if (fault_schedule is not None or link_timeout is not None
                or m.chaos.retries or m.chaos.uplink_drops
                or m.chaos.downlink_drops):
            c = m.chaos
            print(f"[serve] chaos: retries={c.retries} timeouts={c.timeouts} "
                  f"up_drop={c.uplink_drops} down_drop={c.downlink_drops} "
                  f"dup_verdicts_dropped={c.dup_verdicts_dropped} "
                  f"replays={c.verdicts_replayed} "
                  f"link_down={c.link_down_events} "
                  f"link_up={c.link_up_events} "
                  f"degraded_rounds={c.degraded_rounds}")
        if verifiers > 1:
            fs = server.stats
            print(f"[serve] fleet: verifiers={verifiers} "
                  f"downs={fs['verifier_downs']} rejoins={fs['rejoins']} "
                  f"migrations={fs['migrations']} reopens={fs['reopens']} "
                  f"redispatches={fs['redispatches']} "
                  f"lost_verdicts={fs['lost_verdicts']}")
        for i, dev in enumerate(edges[:4]):
            if dev.session is not None:
                print(f"[serve] dev{i} response: {dev.response_tokens[:12]}")
    return {"stats": stats, "total": total, "edges": edges, "server": server,
            "metrics": m, "result": result}


def _run_lockstep(server, edges, fleet, rounds, net, verbose):
    """The original synchronous round loop (reference / ``--sync``): all
    devices draft, the pool drains through dispatch epochs, verdicts apply,
    repeat.  No drafting/verification overlap exists, so WDT here is the
    analytic accounting of `core/wdt.py`, not a measurement.

    This driver deliberately sticks to the LEGACY channels — the
    ``open_session`` handle's synchronous ``first_token`` and the
    ``step()`` verdict return list — so the event-driven runtime's
    stream-equivalence guarantee is checked against a consumer of the
    deprecation shims (tests/test_policies.py)."""
    stats = []
    for sp, dev in zip(fleet, edges):
        # synchronous driver: every device must be admitted up front, so
        # fail loudly on capacity exhaustion instead of queueing
        handle = server.open_session(sp.idx, sp.prompt,
                                     slo_class=sp.slo_class,
                                     draft_speed=sp.draft_speed,
                                     queue_on_full=False)
        dev.start_session(sp.idx, sp.prompt, handle.first_token)
        stats.append(WDTStats())

    now = 0.0
    t_wall0 = time.time()
    for r in range(rounds):
        # all devices draft and submit (synchronous round model on CPU)
        results = {}
        for i, dev in enumerate(edges):
            res = dev.draft_round()
            t_net = net.round_trip(res.n_sent, res.q_payload())
            server.submit(i, res.tokens, res.q_logits,
                          q_compact=res.q_compact, now=now,
                          t_draft=res.draft_time, t_network=t_net)
            results[i] = (res, t_net)
        # dispatch epochs until the pool drains
        while server.queue_depth:
            verdicts = server.step(now)
            if not verdicts:
                now += 0.005   # idle epoch: advance time to unblock criticals
                continue
            server.pop_events()   # discard the mirrored event stream: this
            # driver reads the legacy channels, and an undrained event
            # buffer would otherwise grow per round in long runs
            for v in verdicts:
                res, t_net = results[v.session_id]
                edges[v.session_id].apply_verdict(
                    v.accept_len, v.token, res.tokens
                )
                edges[v.session_id].observe_verdict(
                    v.accept_len, res.k_used, rtt=t_net,
                    queue_depth=getattr(v, "queue_depth", None),
                    features=res.features,
                )
                stats[v.session_id].add(
                    IterationLog(
                        session_id=v.session_id,
                        round_index=r,
                        n_drafted=res.n_drafted,
                        n_sent=res.n_sent,
                        n_accepted=v.accept_len,
                        n_committed=v.emitted,
                        t_draft=res.draft_time,
                        t_network=t_net,
                        t_queue=v.t_queue,
                        t_verify=v.t_verify,
                        violated=v.violated,
                        k_used=res.k_used,
                    ),
                    tau_d=1.0 / edges[v.session_id].controller.draft_speed,
                )
            now += 0.01
    wall = time.time() - t_wall0

    total = WDTStats()
    for s in stats:
        total.iterations += s.iterations
        total.drafted += s.drafted
        total.sent += s.sent
        total.accepted += s.accepted
        total.committed += s.committed
        total.wasted += s.wasted
        total.violations += s.violations
    if verbose:
        engine = server.engine
        print(f"[serve] mode=sync devices={len(edges)} rounds={rounds} "
              f"policy={server.policy}")
        print(f"[serve] drafted={total.drafted} accepted={total.accepted} "
              f"committed={total.committed} waste_frac={total.waste_fraction:.3f} "
              f"acceptance={total.acceptance_rate:.3f}")
        print(f"[serve] engine batches={engine.stats['batches']} wall={wall:.1f}s")
        for i, dev in enumerate(edges[:4]):
            print(f"[serve] dev{i} response: {dev.response_tokens[:12]}")
    return {"stats": stats, "total": total, "edges": edges, "server": server}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--target", default="qwen2-7b")
    ap.add_argument("--draft", default=None)
    ap.add_argument("--self-draft", action="store_true",
                    help="edges draft with the target's own params: no "
                         "second model (what fits one chip at published "
                         "widths)")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="tiny same-family widths (the default); "
                         "--no-reduced serves the config's published widths")
    ap.add_argument("--devices", type=int, default=4)
    ap.add_argument("--rounds", type=int, default=8)
    ap.add_argument("--k-max", type=int, default=6)
    ap.add_argument("--policy", default="wisp",
                    choices=(*available_policies(), "slo"),
                    help="batch-selection policy from the scheduling "
                         "registry ('slo' is a legacy alias of 'wisp')")
    ap.add_argument("--scheduler", dest="policy", help=argparse.SUPPRESS)
    ap.add_argument("--spec-policy", default="static",
                    choices=tuple(available_spec_policies()),
                    help="per-session draft-length policy from the "
                         "speculation-controller registry (DESIGN.md §11): "
                         "static (K = k_max every block) or adaptive "
                         "(per-block K from acceptance/RTT/verifier load)")
    ap.add_argument("--predictor-path", default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sync", action="store_true",
                    help="lock-step reference driver (no overlap)")
    ap.add_argument("--no-speculate", action="store_true",
                    help="event-driven but without speculative continuation")
    ap.add_argument("--churn", action="store_true",
                    help="session churn (Poisson think times) until --horizon")
    ap.add_argument("--horizon", type=float, default=20.0,
                    help="virtual-seconds horizon for --churn")
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--prefill", choices=("zero", "monolithic", "chunked"),
                    default="zero",
                    help="how prompt prefill is charged on the virtual "
                         "clock (DESIGN.md §8)")
    ap.add_argument("--prefill-chunk", type=int, default=32,
                    help="prompt tokens per schedulable prefill chunk")
    ap.add_argument("--q-mode", choices=("dense", "compact", "none"),
                    default="dense",
                    help="draft q payload: dense (K,V) logits, compact "
                         "top-C table (O(K*C) uplink), or none (greedy)")
    ap.add_argument("--q-top-c", type=int, default=64,
                    help="top-C table width for --q-mode compact")
    ap.add_argument("--verifiers", type=int, default=1,
                    help="verifier replicas behind the prefix-locality "
                         "router (repro.fleet); 1 = single-server runtime")
    ap.add_argument("--fail-at", action="append", default=[],
                    metavar="IDX:T0[:T1]",
                    help="DEPRECATED (compiles onto --fault-schedule): kill "
                         "verifier IDX at virtual time T0 (recover at T1 if "
                         "given); repeatable")
    ap.add_argument("--straggle", action="append", default=[],
                    metavar="IDX:T0:T1:FACTOR",
                    help="DEPRECATED (compiles onto --fault-schedule): slow "
                         "verifier IDX's epochs by FACTOR in [T0,T1); "
                         "repeatable")
    ap.add_argument("--fault-schedule", default=None, metavar="SPEC",
                    help="seeded chaos plan (DESIGN.md §14): a preset "
                         "('lossy', 'flap', 'storm') or a DSL string, e.g. "
                         "'drop=0.1,dup=0.05,linkdown@0.25+0.5,seed=7,"
                         "kill=0@0.5'")
    ap.add_argument("--link-timeout", type=float, default=None,
                    metavar="SECONDS",
                    help="edge per-round timeout before an idempotent "
                         "re-submission (exponential backoff + jitter); "
                         "unset = no retries")
    ap.add_argument("--link-degrade", action="store_true",
                    help="let link health degrade speculation depth "
                         "(K shrinks under flap, K=1 while the link is "
                         "down; changes committed streams like adaptive-K)")
    ap.add_argument("--link-jitter", type=float, default=0.0,
                    metavar="SIGMA",
                    help="per-message log-normal latency jitter sigma on "
                         "the modelled network (seeded; 0 = fixed RTT)")
    ap.add_argument("--kv-tier", type=int, default=0, metavar="PAGES",
                    help="host-DRAM KV spill pool size in pages under each "
                         "verifier's device page pool (DESIGN.md §12); "
                         "0 = no tier")
    ap.add_argument("--spill-quantize", action="store_true",
                    help="int8-quantize KV pages on spill (per-page scales; "
                         "stored only when the dequantization round-trips "
                         "bit-exactly, raw fallback otherwise)")
    ap.add_argument("--spill-idle", type=int, default=2,
                    metavar="EPOCHS",
                    help="engine dispatches a session must sit idle before "
                         "its pages become spill candidates")
    ap.add_argument("--tenant-mix", default=None,
                    choices=tuple(sorted(TENANT_MIXES)),
                    help="named multi-tenant workload mix (DESIGN.md §13): "
                         "splits the fleet into per-tenant device groups "
                         "and applies their admission contracts")
    ap.add_argument("--tenants", action="append", default=[],
                    metavar="NAME[:KEY=VAL]*",
                    help="add/override a tenant registry entry, e.g. "
                         "flood:weight=1:rate=600:burst=128:queued=2; "
                         "keys: weight, slo, rate, burst, inflight, conc, "
                         "queued; repeatable")
    args = ap.parse_args()

    def _parse_fail(spec: str) -> tuple:
        parts = spec.split(":")
        if len(parts) not in (2, 3):
            raise SystemExit(f"--fail-at wants IDX:T0[:T1], got {spec!r}")
        return (int(parts[0]), float(parts[1]),
                float(parts[2]) if len(parts) == 3 else None)

    def _parse_straggle(spec: str) -> tuple:
        parts = spec.split(":")
        if len(parts) != 4:
            raise SystemExit(f"--straggle wants IDX:T0:T1:FACTOR, got {spec!r}")
        return (int(parts[0]), float(parts[1]), float(parts[2]),
                float(parts[3]))

    if args.fail_at or args.straggle:
        warnings.warn(
            "--fail-at / --straggle are deprecated; use --fault-schedule "
            "(e.g. 'kill=0@0.5' / 'straggle=1@0.05+0.95*400') — the legacy "
            "flags compile onto the schedule for now",
            DeprecationWarning, stacklevel=2,
        )
    enable_compile_cache()
    pred = RejectionPredictor.load(args.predictor_path) if args.predictor_path else None
    run_serving(
        args.target, args.draft, reduced=args.reduced,
        devices=args.devices, rounds=args.rounds,
        k_max=args.k_max, policy=args.policy, predictor=pred,
        self_draft=args.self_draft,
        spec_policy=args.spec_policy,
        seed=args.seed, sync=args.sync, speculate=not args.no_speculate,
        churn=args.churn, horizon=args.horizon if args.churn else None,
        prompt_len=args.prompt_len, prefill_mode=args.prefill,
        prefill_chunk_tokens=args.prefill_chunk,
        q_mode=args.q_mode, q_top_c=args.q_top_c,
        verifiers=args.verifiers,
        fail_at=tuple(_parse_fail(s) for s in args.fail_at),
        straggle=tuple(_parse_straggle(s) for s in args.straggle),
        fault_schedule=args.fault_schedule,
        link_timeout=args.link_timeout,
        link_degrade=args.link_degrade,
        link_jitter=args.link_jitter,
        kv_tier_pages=args.kv_tier,
        spill_quantize=args.spill_quantize,
        spill_idle_epochs=args.spill_idle,
        tenant_mix=args.tenant_mix,
        tenants=tuple(args.tenants),
    )


if __name__ == "__main__":
    main()
