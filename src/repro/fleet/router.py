"""Verifier-fleet router: prefix-locality placement + failover plumbing.

One `FleetRouter` fronts N independent `WISPServer` verifiers (each with
its own engine, page pool and SLO scheduler) and owns the three fleet
concerns (docs/ARCHITECTURE.md §7):

  * **placement** — a new session routes to the alive verifier whose
    content-addressed prefix index (`PageAllocator.prefix_index`, the
    chained page hashes from PR 1) covers the longest leading stretch of
    its prompt; on a tie or full miss, to the least-loaded verifier.
    The walk is read-only: routing must not perturb cache hit/refcount
    accounting.
  * **liveness** — a `HeartbeatMonitor` declares verifiers dead after a
    missed-beat window and fires death/rejoin hooks that keep the
    `HedgedDispatcher`'s rotation in sync (the ISSUE-6 membership bug);
  * **failover** — every in-flight verify round is tracked under the
    idempotency key ``(session_id, round_index)``; verdicts are delivered
    owner-authoritatively (a verdict from a verifier that no longer owns
    the session is dropped — the re-dispatched round on the new owner is
    the one that advances the device) and deduped through the
    dispatcher's first-wins commit.  Dead or straggling verifiers hand
    their sessions over via `migrate_session`: the committed stream is
    replayed as a chunked prefill (`WISPServer.restore_session`) on the
    destination, which is lossless under rng-tagged verification
    (DESIGN.md §10).

The router is driver-agnostic: `repro.fleet.runtime.FleetRuntime` drives
it on the virtual clock, but every method is plain synchronous Python.
Events drain as ``(verifier_id, ServerEvent)`` pairs via ``pop_events``;
events emitted by a verifier that lost ownership of the session in the
meantime are filtered out (stale-owner events would double-deliver).
"""
from __future__ import annotations

import dataclasses

from repro.core.estimator import BatchShape
from repro.runtime.failure import HeartbeatMonitor
from repro.runtime.straggler import HedgedDispatcher
from repro.serving.engine import NoFreeSlots
from repro.serving.kv_cache import OutOfPages
from repro.serving.events import Migrated, VerifierDown
from repro.tenancy import DEFAULT_TENANT


class FleetCapacityError(RuntimeError):
    """No alive verifier can take the session (all dead, or none has the
    slots/pages a restore needs)."""


@dataclasses.dataclass
class SessionMeta:
    """Router-side soft state per session (survives verifier death).

    ``alpha`` / ``spec_k`` replicate the owner's live adaptive-speculation
    context (EWMA acceptance, last draft-length cap) — refreshed on every
    submit, when the owner is by construction alive — so a migrated
    session's restore does NOT reset them to cold-start defaults (the
    adaptive-K controller would otherwise re-converge from scratch after
    every failover)."""

    slo_class: int
    draft_speed: float
    extras: object = None
    alpha: float = 0.6
    spec_k: int = 0
    #: owning tenant (DESIGN.md §13) — must survive migration so the
    #: restored session keeps its fair-share weight and budget accounting
    tenant: str = DEFAULT_TENANT


class FleetRouter:
    """Routes sessions across verifiers; see module docstring."""

    def __init__(
        self,
        verifiers,
        *,
        heartbeat_timeout: float = 0.15,
        hedge_factor: float = 8.0,
        hedge_guard: float = 0.01,
    ):
        if not verifiers:
            raise ValueError("need at least one verifier")
        if isinstance(verifiers, dict):
            self.verifiers = dict(verifiers)
        else:
            self.verifiers = {f"v{i}": srv for i, srv in enumerate(verifiers)}
        self.monitor = HeartbeatMonitor(
            timeout=heartbeat_timeout,
            on_death=self._on_death,
            on_rejoin=self._on_rejoin,
        )
        for vid in self.verifiers:
            self.monitor.register(vid, 0.0)
        self.dispatcher = HedgedDispatcher(
            list(self.verifiers), guard=hedge_guard, hedge_factor=hedge_factor
        )
        #: session id -> verifier id currently authoritative for it
        self.owner: dict[int, str] = {}
        self.meta: dict[int, SessionMeta] = {}
        self._events: list[tuple] = []      # (vid, ServerEvent)
        self.stats = {
            "opened": 0,
            "migrations": 0,
            "reopens": 0,
            "redispatches": 0,
            "verifier_downs": 0,
            "rejoins": 0,
            "stale_events_dropped": 0,
            "dropped_verdicts": 0,
            "lost_verdicts": 0,
            "replayed_verdicts": 0,
        }

    # -- uniform-fleet conveniences (the runtime reads these) ----------------
    @property
    def network(self):
        return next(iter(self.verifiers.values())).network

    @property
    def slo_classes(self):
        return next(iter(self.verifiers.values())).slo_classes

    @property
    def coeffs(self):
        return next(iter(self.verifiers.values())).coeffs

    @property
    def policy(self):
        return next(iter(self.verifiers.values())).policy

    @property
    def ttft_slo(self):
        return next(iter(self.verifiers.values())).ttft_slo

    @property
    def prefill_log(self):
        """Fleet-wide view of the verifiers' completed chunked prefills."""
        return [r for v in self.verifiers.values() for r in v.prefill_log]

    @property
    def engines(self):
        return [v.engine for v in self.verifiers.values()]

    # -- liveness ------------------------------------------------------------
    def beat(self, vid: str, now: float) -> None:
        self.monitor.beat(vid, now)

    def sweep(self, now: float) -> list[str]:
        """Heartbeat sweep; returns verifiers newly declared dead (their
        death hooks — dispatcher removal, VERIFIER_DOWN event — already
        ran).  The caller migrates the dead verifiers' sessions."""
        return self.monitor.sweep(now)

    def sweep_hedges(self, now: float) -> list[tuple]:
        """Straggler sweep: in-flight rounds past their hedge deadline,
        as ``((session_id, round_index), backup_vid)`` pairs."""
        return self.dispatcher.sweep(now)

    def alive_ids(self) -> list[str]:
        return [v for v in self.verifiers if self.monitor.peers[v].alive]

    def _on_death(self, vid: str, now: float) -> None:
        self.stats["verifier_downs"] += 1
        self.dispatcher.remove_replica(vid)
        self._events.append((vid, VerifierDown(-1, now, vid)))

    def _on_rejoin(self, vid: str, now: float) -> None:
        self.stats["rejoins"] += 1
        self.dispatcher.add_replica(vid)

    # -- placement -----------------------------------------------------------
    def _prefix_coverage(self, vid: str, tokens) -> int:
        """Leading tokens of ``tokens`` resident in the verifier's prefix
        index, by the read-only chained-page-hash walk (no hit/refcount
        mutation — this is a routing probe, not an open)."""
        engine = self.verifiers[vid].engine
        if not getattr(engine, "paged", False):
            return 0
        alloc = engine.kv.allocator
        ps = alloc.page_size
        h = b"root"
        n = 0
        for s in range(0, len(tokens) - ps + 1, ps):
            h = alloc.chain_hash(h, tokens[s:s + ps])
            if h not in alloc.prefix_index:
                break
            n += ps
        return n

    def _load(self, vid: str) -> int:
        srv = self.verifiers[vid]
        return len(srv.sessions) + len(srv.prefilling) + len(srv.admission_queue)

    def route(self, prompt_tokens, exclude=()) -> str:
        """Pick a verifier for a prompt: longest prefix-index coverage
        among alive candidates, falling back to least-loaded (ties break
        on the verifier id, which self-balances: the winner's load rises
        by one and the next tie goes elsewhere)."""
        alive = [v for v in self.alive_ids() if v not in exclude]
        if not alive:
            raise FleetCapacityError("no alive verifier to route to")
        best, best_cov = None, 0
        for vid in alive:
            cov = self._prefix_coverage(vid, prompt_tokens)
            if cov > best_cov:
                best, best_cov = vid, cov
        if best is not None:
            return best
        return min(alive, key=lambda v: (self._load(v), v))

    # -- session lifecycle ---------------------------------------------------
    def open_session(self, session_id: int, prompt_tokens, *,
                     slo_class: int | None = None, draft_speed: float = 50.0,
                     extras=None, now: float = 0.0,
                     tenant: str = DEFAULT_TENANT) -> str:
        vid = self.route(prompt_tokens)
        self.owner[session_id] = vid
        srv = self.verifiers[vid]
        srv.open_session(
            session_id, prompt_tokens, slo_class=slo_class,
            draft_speed=draft_speed, extras=extras, queue_on_full=True,
            now=now, tenant=tenant,
        )
        # record the RESOLVED class (tenant default applied server-side)
        # so a migration restores the same contract
        spec = srv.tenants.get(tenant).spec
        if slo_class is None:
            slo_class = spec.slo_class if spec.slo_class is not None else 3
        self.meta[session_id] = SessionMeta(
            slo_class, draft_speed, extras, tenant=tenant,
        )
        self.stats["opened"] += 1
        self._drain(vid)
        return vid

    def close_session(self, session_id: int, now: float = 0.0) -> str | None:
        vid = self.owner.pop(session_id, None)
        self.meta.pop(session_id, None)
        self.dispatcher.inflight = {
            k: f for k, f in self.dispatcher.inflight.items()
            if k[0] != session_id
        }
        if vid is None:
            return None
        self.verifiers[vid].close_session(session_id, now=now)
        self._drain(vid)
        return vid

    def sessions_on(self, vid: str) -> list[int]:
        return sorted(s for s, v in self.owner.items() if v == vid)

    # -- request path --------------------------------------------------------
    def _track(self, session_id: int, vid: str, n_draft: int, now: float,
               hedged: bool) -> None:
        srv = self.verifiers[vid]
        s = srv.sessions[session_id]
        eta = srv.coeffs.predict([BatchShape(
            new_tokens=n_draft + 1, cached_tokens=s.committed_len - 1,
        )])
        # replicate the session's adaptive-speculation context into the
        # router's soft state while the owner is alive: a later migration
        # restores alpha/spec_k instead of cold-start defaults
        m = self.meta.get(session_id)
        if m is not None:
            m.alpha, m.spec_k = s.alpha, s.spec_k
        key = (session_id, s.rounds)
        self.dispatcher.track(key, vid, float(eta), now)
        if hedged:
            self.dispatcher.inflight[key].hedged = True

    def submit(self, session_id: int, draft_tokens, q_logits=None, *,
               q_compact=None, now: float, t_draft: float,
               t_network: float, round_index: int | None = None) -> str:
        """Queue a drafted block on the session's owner; the round enters
        the dispatcher's in-flight tracking under (session_id, rounds).
        A duplicate the owner absorbed (idempotent ``WISPServer.submit``
        returned None) is NOT re-tracked: tracking an already-committed
        round would leave a stale in-flight entry the straggler sweep
        hedges forever."""
        vid = self.owner[session_id]
        srv = self.verifiers[vid]
        rid = srv.submit(session_id, draft_tokens, q_logits,
                         q_compact=q_compact, now=now, t_draft=t_draft,
                         t_network=t_network, round_index=round_index)
        if rid is not None:
            self._track(session_id, vid, len(draft_tokens), now,
                        hedged=False)
        self._drain(vid)
        return vid

    def resubmit(self, session_id: int, draft_tokens, q_logits=None, *,
                 q_compact=None, now: float, t_draft: float,
                 t_network: float, round_index: int | None = None) -> str:
        """Re-dispatch an in-flight round to the session's (new) owner
        after a migration; marked hedged so the sweep never re-hedges it."""
        vid = self.owner[session_id]
        srv = self.verifiers[vid]
        rid = srv.submit(session_id, draft_tokens, q_logits,
                         q_compact=q_compact, now=now, t_draft=t_draft,
                         t_network=t_network, round_index=round_index)
        if rid is not None:
            self._track(session_id, vid, len(draft_tokens), now,
                        hedged=True)
        self.stats["redispatches"] += 1
        self._drain(vid)
        return vid

    def step(self, vid: str, now: float, *, verify_time=None) -> list:
        verdicts = self.verifiers[vid].step(now, verify_time=verify_time)
        self._drain(vid)
        return verdicts

    def queue_depth(self, vid: str) -> int:
        return self.verifiers[vid].queue_depth

    # -- failover ------------------------------------------------------------
    def migrate_session(self, session_id: int, committed_tokens, *,
                        rounds: int, now: float = 0.0,
                        target: str | None = None) -> tuple[str, int]:
        """Move a session off its owner by replaying its committed stream
        (device-side ground truth) as a prefill on a destination picked by
        prefix locality (the dead verifier may not be the only one holding
        the prefix) then least-loaded.  Returns ``(dst, replayed_tokens)``.

        ``rounds`` must be the device's delivered-verdict count: the
        restored server session resumes the (session_id, round_index)
        keying exactly where the device left it, so re-dispatched rounds
        collide with — and are deduped against — their lost originals."""
        src = self.owner[session_id]
        m = self.meta[session_id]
        committed = [int(t) for t in committed_tokens]
        candidates = [v for v in self.alive_ids() if v != src]
        if target in candidates:
            candidates.remove(target)
            candidates.insert(0, target)
        else:
            ordered = self.route(committed, exclude=(src,))
            candidates.remove(ordered)
            candidates.insert(0, ordered)
        last_err = None
        for dst in candidates:
            try:
                replayed = self.verifiers[dst].restore_session(
                    session_id, committed, slo_class=m.slo_class,
                    draft_speed=m.draft_speed, rounds=rounds,
                    alpha=m.alpha, spec_k=m.spec_k,
                    extras=m.extras, now=now, tenant=m.tenant,
                )
            except (OutOfPages, NoFreeSlots) as e:
                last_err = e
                continue
            self.owner[session_id] = dst
            # tear down the source copy AFTER ownership moved: its CLOSED
            # (and any queued-admission) events now fail the owner filter
            if self._has_session(src, session_id):
                self.verifiers[src].close_session(session_id, now=now)
            self._drain(src)
            self._drain(dst)
            self.stats["migrations"] += 1
            self._events.append((dst, Migrated(
                session_id, now, src, dst, replayed)))
            return dst, replayed
        raise FleetCapacityError(
            f"no verifier can restore session {session_id}"
        ) from last_err

    def reopen_session(self, session_id: int, prompt_tokens,
                       now: float = 0.0) -> str:
        """Failover for a session that never started streaming (queued or
        still prefilling on a dead verifier): cancel the source copy and
        open it afresh elsewhere — nothing committed, nothing to replay."""
        src = self.owner[session_id]
        m = self.meta[session_id]
        dst = self.route(prompt_tokens, exclude=(src,))
        self.owner[session_id] = dst
        if self._has_session(src, session_id):
            self.verifiers[src].close_session(session_id, now=now)
        self._drain(src)
        self.verifiers[dst].open_session(
            session_id, prompt_tokens, slo_class=m.slo_class,
            draft_speed=m.draft_speed, extras=m.extras, queue_on_full=True,
            now=now, tenant=m.tenant,
        )
        self.stats["reopens"] += 1
        self._drain(dst)
        self._events.append((dst, Migrated(session_id, now, src, dst, 0)))
        return dst

    def scrub(self, vid: str) -> None:
        """Post-failover cleanup of a dead verifier's host-side state:
        close any leftover sessions (their owners have all moved, so the
        events are filtered) and empty its pending pool."""
        srv = self.verifiers[vid]
        for sid in (set(srv.sessions) | set(srv.prefilling)
                    | {e[0] for e in srv.admission_queue}
                    | srv.throttled_session_ids()):
            srv.close_session(sid, now=srv.now)
        srv.pending = []
        self._drain(vid)

    def deliver_verdict(self, vid: str, verdict) -> bool:
        """Delivery-time gate: owner-authoritative + idempotent.  A verdict
        from a verifier that lost the session (migration raced it) is
        dropped — the re-dispatched round on the new owner advances the
        device instead, keeping device and owner state in lockstep.  The
        dispatcher's first-wins commit on (session_id, round_index)
        dedupes the round; an owner-sent copy of an already-committed
        round is a *replay* (the original verdict died on a flaky
        downlink and the device re-submitted, DESIGN.md §14) and IS
        delivered — the device's own round gate absorbs true duplicates."""
        sid = verdict.session_id
        if self.owner.get(sid) != vid:
            self.stats["dropped_verdicts"] += 1
            return False
        if not self.dispatcher.commit((sid, verdict.round_index)):
            self.stats["replayed_verdicts"] += 1
        return True

    def note_lost_verdict(self) -> None:
        """A verdict's epoch never finished (verifier died mid-epoch)."""
        self.stats["lost_verdicts"] += 1

    # -- event stream --------------------------------------------------------
    def _has_session(self, vid: str, sid: int) -> bool:
        srv = self.verifiers[vid]
        return (sid in srv.sessions or sid in srv.prefilling
                or sid in srv.admission_queue
                or sid in srv.throttled_session_ids())

    def _drain(self, vid: str) -> None:
        for ev in self.verifiers[vid].pop_events():
            if self.owner.get(ev.session_id) != vid:
                self.stats["stale_events_dropped"] += 1
                continue
            self._events.append((vid, ev))
            if ev.kind == "REJECTED":
                # terminal for the session: release router ownership so a
                # retry under the same id routes (and counts) fresh
                self.owner.pop(ev.session_id, None)
                self.meta.pop(ev.session_id, None)

    def pop_events(self) -> list[tuple]:
        """Drain the merged fleet stream as (verifier_id, ServerEvent)
        pairs: every verifier's surviving (owner-matching) events plus the
        router's own MIGRATED / VERIFIER_DOWN emissions, in order."""
        out, self._events = self._events, []
        return out
