"""WISP verification server: queues + pluggable scheduling policy + engine.

The coordinator keeps per-session state (slot, committed tokens, EWMA
acceptance estimate), maintains the pending-work pool, and at each
dispatch epoch runs the selected `SchedulingPolicy` (``"wisp"`` =
Algorithm 1; ``"fcfs"`` / ``"edf"`` / ``"priority"`` baselines — see
`repro.core.scheduler`) to build a batch, executes it on the
verification engine, and publishes the outcomes.

**Every outcome flows through one ordered event stream** (docs/API.md):
``open_session`` returns a `SessionHandle`; admissions, first tokens,
verify verdicts, preemptions, TTFT records and closes surface as typed
`ServerEvent`s drained with ``pop_events()``.  The legacy channels —
``pop_admissions()`` polling, the ``step()`` verdict return list, the
``prefill_log`` side-car — still work as thin deprecation shims and
carry byte-identical results (tests/test_policies.py).

Prompt prefill runs in one of two modes (DESIGN.md §8):

  * ``prefill="monolithic"`` (default) — ``open_session`` runs the whole
    prompt as one blocking engine call; the handle is ``active`` with its
    ``first_token`` set on return (the legacy path; simple drivers and
    the lock-step reference need it);
  * ``prefill="chunked"`` — ``open_session`` only *admits* the session
    (allocating its slot/pages) and returns a ``prefilling`` handle; the
    prompt is split into fixed-budget chunks that enter the pending pool
    as `PrefillChunkWork` items with the session's TTFT deadline and
    compete with verification under the scheduling policy.  The first
    token surfaces as a ``FIRST_TOKEN`` event when the final chunk lands.

This is the *functional* server used by examples and integration tests
(driven synchronously, CPU).  Paper-scale capacity/goodput numbers come
from `repro.sim`, which replays the same policies against the analytic
latency model at thousands of devices.
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from collections import deque

import numpy as np

from repro.core.estimator import EstimatorCoeffs
from repro.core.scheduler import (
    PrefillChunkWork,
    SchedulerConfig,
    VerifyWork,
    make_policy,
)
from repro.serving.engine import NoFreeSlots, VerificationEngine
from repro.serving.events import (
    Admitted,
    Closed,
    FirstToken,
    Preempted,
    Rejected,
    ServerEvent,
    SessionHandle,
    Throttled,
    TTFTRecord,
    VerdictEvent,
)
from repro.serving.kv_cache import OutOfPages
from repro.serving.tracing import span
from repro.serving.transport import NetworkModel
from repro.tenancy import DEFAULT_TENANT, Stage, TenantRegistry

#: paper §5.1: four token-speed SLO classes (tokens/s)
DEFAULT_SLO_CLASSES = {1: 8.0, 2: 6.0, 3: 4.0, 4: 2.0}

#: TTFT (time-to-first-token) budgets per SLO class, seconds — the
#: deadline chunked prefill schedules against (DESIGN.md §8).  Scaled like
#: the token-speed classes: a class promising 8 tok/s streaming also
#: promises a snappier first token than the 2 tok/s tier.
DEFAULT_TTFT_SLO = {1: 0.75, 2: 1.5, 3: 3.0, 4: 6.0}


@dataclasses.dataclass
class ServerSession:
    session_id: int
    slot: int
    slo_class: int
    committed_len: int
    alpha: float = 0.6           # EWMA acceptance-rate estimate
    rounds: int = 0
    draft_speed: float = 50.0
    t_draft_last: float = 0.0
    t_net_last: float = 0.0
    #: the edge speculation controller's last-submitted draft-length cap
    #: (DESIGN.md §11) — server-side observability, and carried through
    #: fleet migration so a restored session's adaptive-K context (like
    #: its ``alpha``) survives verifier death
    spec_k: int = 0
    #: owning tenant (DESIGN.md §13) — stamped onto every work item the
    #: session submits so the ``"wfq"`` policy can bucket virtual time
    tenant: str = DEFAULT_TENANT


@dataclasses.dataclass
class PrefillingSession:
    """A session whose prompt is still being chunk-prefilled: admitted to
    the engine (slot + pages held, ``state`` resumable) but not yet
    streaming.  Exactly one chunk of it is in the pending pool at a time —
    chunk *i+1* depends on chunk *i*'s KV."""

    session_id: int
    state: object                # engine PrefillState
    slo_class: int
    draft_speed: float
    t_request: float             # when the client asked (TTFT clock start)
    deadline: float              # TTFT deadline = t_request + ttft_slo[class]
    tenant: str = DEFAULT_TENANT
    #: the tenant's rate limiter borrowed from the debt band for this
    #: open — the session's prefill chunks run at reduced WFQ weight
    deprioritized: bool = False


@dataclasses.dataclass
class PrefillRecord:
    """One completed chunked prefill (the TTFT observability unit)."""

    session_id: int
    prompt_len: int
    chunks: int
    t_request: float
    t_first: float               # when the final chunk's epoch completed
    deadline: float
    violated: bool

    @property
    def ttft(self) -> float:
        return self.t_first - self.t_request


@dataclasses.dataclass
class Verdict:
    session_id: int
    accept_len: int
    token: int
    emitted: int
    t_queue: float
    t_verify: float
    deadline: float
    violated: bool
    #: which verify round of the session this verdict resolves — the
    #: second half of the fleet's idempotency key (session_id, round_index)
    #: for hedged re-dispatch (repro.fleet); -1 on legacy paths
    round_index: int = -1
    #: the verifier's pending-pool depth when this verdict committed —
    #: piggybacked load feedback the edge speculation controller's
    #: congestion brake consumes (DESIGN.md §11); no extra round trip
    queue_depth: int = 0


class AdmissionQueue:
    """FIFO admission-retry queue with O(1) pops and O(1) cancellation.

    This queue is on the per-epoch hot path under churn (``_try_admit``
    runs every dispatch epoch and every close): a plain list cost O(n)
    per admission (``pop(0)``) and a full rebuild per cancellation.  Here
    admissions pop from a `deque` and cancellation just tombstones the
    entry — dead entries are skipped (and dropped) when the FIFO scan
    reaches them.  ``len`` / iteration / membership see only live
    entries.  Entries are tuples whose first element is the session id.

    Tombstones are keyed by a per-push unique token, NOT the session id:
    session ids may be reused (close a queued session, open a new one
    under the same id), and an id-keyed tombstone for the old entry
    would otherwise cancel — or, absorbed into a set, fail to cancel —
    the new one (ghost admission of a closed session)."""

    def __init__(self):
        self._q: deque = deque()            # (token, entry)
        self._dead: set[int] = set()        # cancelled tokens
        self._live: dict[int, int] = {}     # session id -> token
        self._next_token = 0

    def push(self, entry: tuple) -> None:
        sid = entry[0]
        old = self._live.pop(sid, None)
        if old is not None:                 # re-queue supersedes the old entry
            self._dead.add(old)
        self._next_token += 1
        self._q.append((self._next_token, entry))
        self._live[sid] = self._next_token

    def _drop_dead_prefix(self) -> None:
        while self._q and self._q[0][0] in self._dead:
            self._dead.discard(self._q.popleft()[0])

    def peek(self) -> tuple | None:
        """The oldest live entry (or None) — does not remove it."""
        self._drop_dead_prefix()
        return self._q[0][1] if self._q else None

    def popleft(self) -> tuple:
        self._drop_dead_prefix()
        token, entry = self._q.popleft()
        self._live.pop(entry[0], None)
        return entry

    def cancel(self, session_id: int) -> bool:
        """Tombstone a queued session; False when it is not queued."""
        token = self._live.pop(session_id, None)
        if token is None:
            return False
        self._dead.add(token)
        return True

    def resort(self, key) -> None:
        """Re-establish FIFO order after an out-of-order push (preemption
        re-queues a session with its *original* request time).  Rare path:
        O(n log n) is fine here; the hot path stays O(1)."""
        self._q = deque(sorted(
            ((t, e) for t, e in self._q if t not in self._dead),
            key=lambda te: key(te[1]),
        ))
        self._dead.clear()

    def __len__(self) -> int:
        return len(self._live)

    def __bool__(self) -> bool:
        return bool(self._live)

    def __contains__(self, session_id: int) -> bool:
        return session_id in self._live

    def __iter__(self):
        return (e for t, e in self._q if t not in self._dead)


def _work_id(r) -> tuple:
    """``(session_id, round_index)`` of a verify block; of a prefill chunk
    that has run, ``(session_id, chunk index)``: joins a ``wisp.queue``
    interval to the ``wisp.step`` that served it."""
    if r.kind == "prefill":
        return r.session_id, r.payload.state.chunks - 1
    return r.session_id, r.round_index


class WISPServer:
    def __init__(
        self,
        engine: VerificationEngine,
        coeffs: EstimatorCoeffs,
        *,
        policy="wisp",                  # registry name | class | instance
        scheduler: str | None = None,   # DEPRECATED alias of ``policy``
        sched_cfg: SchedulerConfig | None = None,
        slo_classes: dict | None = None,
        network: NetworkModel | None = None,
        dynamic_memory_budget: bool = True,
        deterministic_verify: bool = True,
        prefill: str = "monolithic",    # "monolithic" | "chunked"
        prefill_chunk_tokens: int = 256,
        ttft_slo: dict | None = None,
        tenants=None,   # TenantRegistry | iterable of TenantSpec / spec str
        tracer=None,    # repro.serving.tracing.Tracer: spans of each step
    ):
        self.engine = engine
        #: one tracer for the server and its engine (the engine's own if
        #: the server is given none); None records nothing
        self.tracer = engine.tracer if tracer is None else tracer
        engine.tracer = self.tracer
        self.coeffs = coeffs
        self.sched_cfg = sched_cfg or SchedulerConfig()
        if scheduler is not None:
            warnings.warn(
                "WISPServer(scheduler=...) is deprecated; use policy=... "
                "(registry names: repro.core.scheduler.available_policies())",
                DeprecationWarning, stacklevel=2,
            )
            policy = scheduler
        self.scheduler = make_policy(policy, self.sched_cfg, coeffs)
        #: canonical registry name of the active policy
        self.policy = self.scheduler.name
        self.slo_classes = slo_classes or dict(DEFAULT_SLO_CLASSES)
        self.network = network or NetworkModel()
        if prefill not in ("monolithic", "chunked"):
            raise ValueError(f"unknown prefill mode {prefill!r}")
        #: "monolithic": open_session blocks through the whole prompt.
        #: "chunked": prompts prefill in ``prefill_chunk_tokens``-sized
        #: work items scheduled by the policy against a TTFT deadline.
        self.prefill_mode = prefill
        self.prefill_chunk_tokens = int(prefill_chunk_tokens)
        self.ttft_slo = ttft_slo or dict(DEFAULT_TTFT_SLO)
        #: multi-tenant admission + fair-share source of truth (DESIGN.md
        #: §13).  The default registry is all-unlimited, so a server built
        #: without tenants behaves exactly as before (golden ``tenant/*``
        #: cells pin this).  One registry may be SHARED across a fleet's
        #: servers — budgets are then tenant-global.
        if tenants is None:
            tenants = TenantRegistry()
        elif not isinstance(tenants, TenantRegistry):
            tenants = TenantRegistry(tenants)   # specs / spec strings
        self.tenants = tenants
        #: per-tenant throttle buffers: FIFO of held work, released each
        #: dispatch epoch as the tenant's bucket recovers.  Entries:
        #: ("open", sid, prompt, slo_class, draft_speed, extras,
        #:  t_request, queue_on_full) | ("work", VerifyWork).  Per-tenant
        #: deques so a flooding tenant's backlog head-blocks only itself.
        self._throttled: dict[str, deque] = {}
        #: sid -> tenant for throttle-held opens (state/close lookups)
        self._throttle_held: dict[int, str] = {}
        #: sids shed by the rate limiter (terminal ``"rejected"`` state)
        self._rejected: set[int] = set()
        #: refresh the scheduler's memory budget from the engine's live
        #: free-page capacity every dispatch epoch (paper Eq. 13's M(t_k));
        #: passed to schedule() as an override — the caller's SchedulerConfig
        #: is never mutated
        self.dynamic_memory_budget = dynamic_memory_budget
        #: key each request's accept/correction draws by (session_id,
        #: committed_len) so verification outcomes do not depend on batch
        #: composition or dispatch order — the event-driven and lock-step
        #: drivers then commit identical streams (see VerifyItem.rng_tag)
        self.deterministic_verify = deterministic_verify
        #: the budget the most recent epoch was admitted against
        self.memory_budget_tokens = self.sched_cfg.memory_budget_tokens
        #: observability: the most recent epoch's ScheduleDecision and the
        #: verify time attributed to it (wall by default; virtual when the
        #: cluster runtime passes ``verify_time`` to ``step``)
        self.last_decision = None
        self.last_verify_time = 0.0
        self._dt_virtual = None
        #: server clock: the latest ``now`` any entry point saw (stamps
        #: events from calls that have no time argument of their own)
        self.now = 0.0
        self.sessions: dict[int, ServerSession] = {}
        #: chunked mode: sessions admitted to the engine but still
        #: prefilling (slot held, chunks in the pending pool)
        self.prefilling: dict[int, PrefillingSession] = {}
        #: DEPRECATED side-car of completed chunked prefills — the same
        #: records ride TTFT_RECORD events; kept one release for drivers
        #: reading TTFT logs directly
        self.prefill_log: list[PrefillRecord] = []
        #: times a mutually-blocked prefill was evicted back to the
        #: admission queue (liveness preemption, see ``step``)
        self.prefill_preemptions = 0
        self.pending: list = []          # WorkItem pool
        #: the work items actually executed by the most recent ``step`` —
        #: what the epoch's verify time covers
        self.last_served: list = []
        #: sessions the cache could not admit yet, FIFO-retried each
        #: dispatch epoch; entries: (session_id, prompt, slo_class,
        #: draft_speed, extras, t_request)
        self.admission_queue = AdmissionQueue()
        #: DEPRECATED (sid, first_token) mirror of queued-session /
        #: chunked-prefill FIRST_TOKEN events; drain with pop_admissions()
        self.admitted: list[tuple[int, int]] = []
        #: first committed token per session (feeds SessionHandle)
        self.first_tokens: dict[int, int] = {}
        #: the ordered typed event stream (drain with ``pop_events()``)
        self._events: list[ServerEvent] = []
        self._rid = 0
        self.log: list[Verdict] = []
        #: last committed verdict per live session — the replay cache the
        #: idempotent ``submit`` answers stale re-submissions from (a
        #: device can never be more than one round behind, so one verdict
        #: is all the history a replay ever needs; DESIGN.md §14)
        self._last_verdict: dict[int, Verdict] = {}
        #: idempotency counters (folded into ClusterMetrics.chaos)
        self.chaos_stats = {"dup_submits": 0, "verdict_replays": 0}

    # -- event stream -------------------------------------------------------
    def _emit(self, event: ServerEvent) -> None:
        self._events.append(event)

    def pop_events(self) -> list[ServerEvent]:
        """Drain the typed event stream, in emission order.  THE way to
        observe server outcomes; see docs/API.md for the event types and
        their per-session ordering guarantees.

        A long-running driver must drain this regularly (event-stream
        consumers do so by construction; a legacy-channel driver should
        drain-and-discard, as the lock-step reference does) — the buffer
        grows with every epoch otherwise.  The deprecated mirrors it
        supersedes (``admitted``, ``prefill_log``, ``log``) grow only
        per-session / per-verdict, like the metrics logs."""
        out, self._events = self._events, []
        return out

    def pop_admissions(self) -> list[tuple[int, int]]:
        """DEPRECATED shim: (session_id, first_token) of queued sessions
        admitted — and chunked prefills completed — since the last call.
        Use ``pop_events()`` and match ``FIRST_TOKEN`` events instead."""
        warnings.warn(
            "pop_admissions() is deprecated; drain pop_events() and match "
            "FIRST_TOKEN events",
            DeprecationWarning, stacklevel=2,
        )
        out, self.admitted = self.admitted, []
        return out

    def session_state(self, session_id: int) -> str:
        """Lifecycle state (see `SessionHandle.state`)."""
        if session_id in self.sessions:
            return "active"
        if session_id in self.prefilling:
            return "prefilling"
        if (session_id in self.admission_queue
                or session_id in self._throttle_held):
            return "queued"
        if session_id in self._rejected:
            return "rejected"
        return "closed"

    def throttled_session_ids(self) -> set[int]:
        """Sids of opens currently held by the tenant rate limiter."""
        return set(self._throttle_held)

    # -- sessions -----------------------------------------------------------
    def _register(self, session_id, slot, first, prompt_len, slo_class,
                  draft_speed, tenant=DEFAULT_TENANT) -> int:
        self.sessions[session_id] = ServerSession(
            session_id=session_id,
            slot=slot,
            slo_class=slo_class,
            committed_len=prompt_len + 1,
            draft_speed=draft_speed,
            tenant=tenant,
        )
        self.first_tokens[session_id] = first
        return first

    def _resolve_slo(self, slo_class, spec) -> int:
        """Resolve + validate a session's SLO class: an explicit argument
        wins, else the tenant's default, else class 3.  Unknown classes
        raise a `ValueError` listing the known ones (not a bare KeyError
        deep in ``submit``/``_begin_chunked``)."""
        if slo_class is None:
            slo_class = spec.slo_class if spec.slo_class is not None else 3
        if slo_class not in self.slo_classes:
            raise ValueError(
                f"unknown SLO class {slo_class!r}; known classes: "
                f"{sorted(self.slo_classes)}"
            )
        if self.prefill_mode == "chunked" and slo_class not in self.ttft_slo:
            raise ValueError(
                f"SLO class {slo_class!r} has no TTFT budget; known: "
                f"{sorted(self.ttft_slo)}"
            )
        return slo_class

    def open_session(
        self, session_id: int, prompt_tokens, slo_class: int | None = None,
        draft_speed: float = 50.0, extras=None, queue_on_full: bool = True,
        now: float = 0.0, tenant: str = DEFAULT_TENANT,
    ) -> SessionHandle:
        """Open a session; returns its `SessionHandle`.

        Monolithic prefill: on success the handle is ``active`` with
        ``first_token`` set (the prompt ran as one blocking engine call);
        when the engine is out of KV pages or slots the session is queued
        (``queued`` handle; retried each dispatch epoch, its
        ``FIRST_TOKEN`` event fires on admission) unless
        ``queue_on_full=False``, which re-raises instead.

        Chunked prefill: the handle is ``prefilling`` — admission only
        reserves the slot and enqueues the first prefill chunk (``now``
        starts the TTFT clock); the first token arrives as a
        ``FIRST_TOKEN`` event when the final chunk completes.

        Tenancy (DESIGN.md §13): the ``tenant``'s rate limiter prices the
        open at its prompt length.  A DEPRIORITIZE decision admits but
        serves the prefill at reduced WFQ weight; QUEUE holds the open in
        the tenant's throttle buffer (``queued`` handle; released as the
        bucket recovers); REJECT sheds it outright (``rejected`` handle,
        terminal).  Both emit typed ``THROTTLED``/``REJECTED`` events.
        ``slo_class=None`` resolves to the tenant's default class."""
        self.now = max(self.now, now)
        spec = self.tenants.get(tenant).spec
        slo_class = self._resolve_slo(slo_class, spec)
        self._rejected.discard(session_id)
        handle = SessionHandle(session_id, self)
        stage = self.tenants.admit_session(
            tenant, len(prompt_tokens), now,
            queued=len(self._throttled.get(tenant, ())),
        )
        if stage == Stage.REJECT:
            self._rejected.add(session_id)
            self._emit(Rejected(session_id, now, tenant))
            return handle
        if stage == Stage.QUEUE:
            self._emit(Throttled(session_id, now, tenant, "queue", "open"))
            self._throttled.setdefault(tenant, deque()).append(
                ("open", session_id, list(prompt_tokens), slo_class,
                 draft_speed, extras, now, queue_on_full)
            )
            self._throttle_held[session_id] = tenant
            return handle
        deprio = stage == Stage.DEPRIORITIZE
        if deprio:
            self._emit(Throttled(session_id, now, tenant,
                                 "deprioritize", "open"))
        self._admit_open(session_id, prompt_tokens, slo_class, draft_speed,
                         extras, now, queue_on_full, tenant, deprio)
        return handle

    def _admit_open(self, session_id, prompt_tokens, slo_class, draft_speed,
                    extras, now, queue_on_full, tenant, deprio):
        """The post-throttle half of ``open_session``: engine admission or
        the capacity queue.  Counts the session live for its tenant."""
        st = self.tenants.get(tenant)
        try:
            if self.prefill_mode == "chunked":
                self._begin_chunked(session_id, prompt_tokens, slo_class,
                                    draft_speed, extras, now, tenant, deprio)
                st.live_sessions += 1
                return
            slot, first = self.engine.new_session(prompt_tokens, extras=extras)
        except (OutOfPages, NoFreeSlots):
            if not queue_on_full:
                raise
            self.admission_queue.push(
                (session_id, list(prompt_tokens), slo_class, draft_speed,
                 extras, now, tenant)
            )
            st.live_sessions += 1
            return
        self._register(session_id, slot, first, len(prompt_tokens),
                       slo_class, draft_speed, tenant)
        st.live_sessions += 1
        self._emit(Admitted(session_id, now))
        self._emit(FirstToken(session_id, now, first))

    def _begin_chunked(self, sid, prompt_tokens, slo_class, draft_speed,
                       extras, t_request, tenant=DEFAULT_TENANT,
                       deprio=False):
        """Reserve engine state for a session and enqueue its first prefill
        chunk.  Raises OutOfPages/NoFreeSlots with nothing leaked."""
        state = self.engine.begin_prefill(prompt_tokens, extras=extras)
        ps = PrefillingSession(
            session_id=sid,
            state=state,
            slo_class=slo_class,
            draft_speed=draft_speed,
            t_request=t_request,
            deadline=t_request + self.ttft_slo[slo_class],
            tenant=tenant,
            deprioritized=deprio,
        )
        self.prefilling[sid] = ps
        self._emit(Admitted(sid, self.now))
        # arrival = the ORIGINAL request time, not the (possibly later)
        # admission-retry time: FCFS/utility ordering and queue-time
        # accounting must see the wait the client actually experienced
        self._enqueue_chunk(ps, ps.t_request)

    def _enqueue_chunk(self, ps: PrefillingSession, now: float):
        """Put the session's NEXT prefill chunk in the pending pool (one at
        a time: chunk i+1 attends to chunk i's KV)."""
        st = ps.state
        self._rid += 1
        self.pending.append(PrefillChunkWork(
            req_id=self._rid,
            session_id=ps.session_id,
            slo_class=ps.slo_class,
            arrival=now,
            deadline=ps.deadline,
            draft_len=0,
            cached_len=st.done,
            alpha=0.0,
            payload=ps,
            prefill_tokens=min(self.prefill_chunk_tokens, st.remaining),
            enqueued_at=now,
            t_queued=None if self.tracer is None else time.perf_counter(),
            tenant=ps.tenant,
            tenant_weight=self.tenants.weight(ps.tenant),
            deprioritized=ps.deprioritized,
        ))

    def _try_admit(self):
        """Retry queued sessions in arrival order; stop at the first one
        that still does not fit (FIFO fairness — no small-session bypass)."""
        while True:
            entry = self.admission_queue.peek()
            if entry is None:
                return
            (sid, prompt, slo_class, draft_speed, extras, t_request,
             tenant) = entry
            try:
                if self.prefill_mode == "chunked":
                    # TTFT clock started at the original request — a long
                    # wait in the admission queue is TTFT the client saw
                    self._begin_chunked(sid, prompt, slo_class, draft_speed,
                                        extras, t_request, tenant)
                    self.admission_queue.popleft()
                    continue
                slot, first = self.engine.new_session(prompt, extras=extras)
            except (OutOfPages, NoFreeSlots):
                return
            self.admission_queue.popleft()
            self._register(sid, slot, first, len(prompt), slo_class,
                           draft_speed, tenant)
            self.admitted.append((sid, first))
            self._emit(Admitted(sid, self.now))
            self._emit(FirstToken(sid, self.now, first))

    def _purge_session_work(self, session_id: int, tenant: str) -> None:
        """Drop a closing session's pending + throttle-held verify work and
        refund the tenant's tokens-in-flight accounting."""
        st = self.tenants.get(tenant)
        dropped = 0
        keep = []
        for r in self.pending:
            if r.session_id == session_id:
                if r.kind == "verify":
                    dropped += r.draft_len
            else:
                keep.append(r)
        self.pending = keep
        dq = self._throttled.get(tenant)
        if dq:
            # held blocks were never counted in flight — drop, no refund
            self._throttled[tenant] = deque(
                e for e in dq
                if not (e[0] == "work" and e[1].session_id == session_id)
            )
        st.tokens_in_flight = max(0, st.tokens_in_flight - dropped)

    def close_session(self, session_id: int, now: float | None = None):
        t = self.now if now is None else now
        self.now = max(self.now, t)
        if session_id in self._rejected:
            # shed open: nothing was ever admitted or counted
            self._rejected.discard(session_id)
            self._emit(Closed(session_id, t))
            return
        held = self._throttle_held.pop(session_id, None)
        if held is not None:
            # open still in the tenant's throttle buffer: drop it there
            # (it was never counted live — no decrement)
            self._throttled[held] = deque(
                e for e in self._throttled.get(held, ())
                if not (e[0] == "open" and e[1] == session_id)
            )
            self._emit(Closed(session_id, t))
            return
        s = self.sessions.pop(session_id, None)
        if s is None:
            ps = self.prefilling.pop(session_id, None)
            if ps is not None:
                # cancel mid-prefill: drop the session's queued chunk and
                # release its slot/pages (nothing was published)
                self.pending = [
                    r for r in self.pending if r.session_id != session_id
                ]
                self.engine.abort_prefill(ps.state)
                self._tenant_session_closed(ps.tenant)
                self._emit(Closed(session_id, t))
                self._try_admit()
                return
            # session may still be waiting in the admission queue: cancel it
            tenant = next(
                (e[6] for e in self.admission_queue if e[0] == session_id),
                None,
            )
            if not self.admission_queue.cancel(session_id):
                raise KeyError(session_id)
            if tenant is not None:
                self._tenant_session_closed(tenant)
            self._emit(Closed(session_id, t))
            return
        # Lifecycle rule (docs/ARCHITECTURE.md §"Session lifecycle"): close
        # drops the session's still-pending verification requests.  Leaving
        # them behind would make a later step() dispatch a request whose
        # session — and engine slot — no longer exist (KeyError at best,
        # verification against a recycled slot at worst).
        self._purge_session_work(session_id, s.tenant)
        self.engine.close_session(s.slot)
        self.first_tokens.pop(session_id, None)
        self._last_verdict.pop(session_id, None)
        self._tenant_session_closed(s.tenant)
        self._emit(Closed(session_id, t))
        self._try_admit()

    def _tenant_session_closed(self, tenant: str) -> None:
        st = self.tenants.get(tenant)
        st.live_sessions = max(0, st.live_sessions - 1)

    def restore_session(
        self,
        session_id: int,
        committed_tokens,
        *,
        slo_class: int = 3,
        draft_speed: float = 50.0,
        rounds: int = 0,
        alpha: float = 0.6,
        spec_k: int = 0,
        first_token: int | None = None,
        extras=None,
        now: float = 0.0,
        tenant: str = DEFAULT_TENANT,
    ) -> int:
        """Rebuild a migrated session from its committed token stream
        (the fleet failover path, docs/ARCHITECTURE.md §7).

        The committed stream is the device-side ground truth: everything
        before its last token is replayed as a (resumable, prefix-cache
        aware) prefill — exactly the state the engine invariant requires
        (``fed = committed_len - 1``, KV for ``committed[:-1]``) — and the
        replay's argmax-sampled first token is discarded in favor of the
        stream's actual last token.  With deterministic (rng-tagged)
        verification and same-seed engines the restored session then
        continues byte-identically to the dead verifier (DESIGN.md §10).

        ``rounds`` must be the session's delivered-verdict count so the
        fleet's ``(session_id, round_index)`` hedge keys stay collision
        free across the migration.  Emits NO ADMITTED/FIRST_TOKEN events —
        the client already holds those tokens.  Raises OutOfPages /
        NoFreeSlots (nothing leaked) when this verifier cannot take the
        session; returns the number of prompt tokens actually recomputed
        (prefix-cache hits make migration to a warm verifier nearly
        free)."""
        self.now = max(self.now, now)
        if (session_id in self.sessions or session_id in self.prefilling
                or session_id in self.admission_queue
                or session_id in self._throttle_held):
            raise ValueError(f"session {session_id} already live here")
        committed = [int(t) for t in committed_tokens]
        if len(committed) < 2:
            raise ValueError("restore needs a prompt plus a first token")
        st = self.engine.begin_prefill(committed[:-1], extras=extras)
        try:
            while not st.finished:
                self.engine.prefill_chunk(st, self.prefill_chunk_tokens)
        except OutOfPages:
            self.engine.abort_prefill(st)
            raise
        # the replay sampled a throwaway first token at committed[:-1]'s
        # final position; the stream already committed its successor
        self.engine.last_token[st.slot] = committed[-1]
        self.sessions[session_id] = ServerSession(
            session_id=session_id,
            slot=st.slot,
            slo_class=slo_class,
            committed_len=len(committed),
            alpha=alpha,
            rounds=rounds,
            draft_speed=draft_speed,
            spec_k=spec_k,
            tenant=tenant,
        )
        # migration preserves tenant accounting: the session is live here
        # now (the dead verifier's registry entry — when the registry is
        # fleet-shared, the scrub already decremented it)
        self.tenants.get(tenant).live_sessions += 1
        if first_token is not None:
            self.first_tokens[session_id] = int(first_token)
        return st.total - st.n_cached

    # -- request intake (paper Eq. 6/12: server-side budget -> deadline) ----
    def submit(
        self,
        session_id: int,
        draft_tokens,
        q_logits=None,
        *,
        q_compact=None,
        now: float,
        t_draft: float,
        t_network: float,
        round_index: int | None = None,
    ) -> int | None:
        """Queue a drafted block for verification.  The draft distribution
        arrives as dense ``q_logits`` (exact residual), a `CompactQ` via
        ``q_compact`` (O(K·C) wire payload, DESIGN.md §9), or neither
        (greedy verification reads no q).

        **Idempotent** under the ``(session_id, round_index)`` key
        (DESIGN.md §14): a re-submission of the round currently in flight
        is absorbed (``None``, counted), and a re-submission of an
        already-verified round replays the cached verdict as a fresh
        VERDICT event instead of verifying twice — the committed stream
        advances exactly once per round no matter how many request copies
        a flaky uplink delivers.  ``round_index=None`` (legacy callers on
        a reliable channel) trusts the session's own round counter.

        The session's tenant bucket prices the block at its draft length
        (DESIGN.md §13): DEPRIORITIZE queues it flagged for reduced WFQ
        weight; QUEUE holds it in the tenant's throttle buffer until the
        bucket recovers (released each dispatch epoch).  A streaming
        block is never rejected."""
        self.now = max(self.now, now)
        s = self.sessions[session_id]
        rnd = s.rounds if round_index is None else int(round_index)
        if rnd < s.rounds:
            # stale duplicate of a verified round: its verdict died on the
            # downlink — replay the cached one (new event, new delivery)
            self.chaos_stats["verdict_replays"] += 1
            last = self._last_verdict.get(session_id)
            if last is not None and last.round_index == rnd:
                self._emit(VerdictEvent(session_id, self.now, last))
            return None
        if rnd > s.rounds:
            raise ValueError(
                f"session {session_id}: submit for future round {rnd} "
                f"(server at round {s.rounds})"
            )
        if any(r.kind == "verify" and r.session_id == session_id
               and r.round_index == rnd for r in self.pending) or any(
                e[0] == "work" and e[1].session_id == session_id
                and e[1].round_index == rnd
                for e in self._throttled.get(s.tenant, ())):
            # duplicate of the in-flight round, still queued/held: absorb
            self.chaos_stats["dup_submits"] += 1
            return None
        s.t_draft_last = t_draft
        s.t_net_last = t_network
        target_speed = self.slo_classes[s.slo_class]
        nd = len(draft_tokens)
        s.spec_k = max(nd, 1)
        stage = self.tenants.admit_block(s.tenant, nd, now)
        tstate = self.tenants.get(s.tenant)
        tstate.submitted_tokens += nd
        if stage != Stage.QUEUE:
            # held blocks do not count in flight (else their own release
            # recheck against max_tokens_in_flight would self-block)
            tstate.tokens_in_flight += nd
        # spill tier (DESIGN.md §12): a draft block announces the session's
        # next verify epoch — page its spilled KV back in NOW (best effort)
        # so the fused verify dispatch never blocks on a fault; whatever
        # could not be prefetched is priced into the work item below
        self.engine.prefetch_session(s.slot)
        expected_tokens = s.alpha * nd + 1.0
        budget = expected_tokens / target_speed - t_draft - t_network
        budget = max(budget, 1e-3)
        self._rid += 1
        req = VerifyWork(
            req_id=self._rid,
            session_id=session_id,
            slo_class=s.slo_class,
            arrival=now,
            deadline=now + budget,
            draft_len=nd,
            cached_len=int(self.engine.fed[s.slot]),
            alpha=s.alpha,
            payload=(
                np.asarray(draft_tokens, np.int32),
                None if q_logits is None else np.asarray(q_logits),
                q_compact,
            ),
            enqueued_at=now,
            t_queued=None if self.tracer is None else time.perf_counter(),
            round_index=s.rounds,
            pagein_tokens=self.engine.spilled_tokens(s.slot),
            tenant=s.tenant,
            tenant_weight=self.tenants.weight(s.tenant),
            deprioritized=stage == Stage.DEPRIORITIZE,
        )
        if stage == Stage.QUEUE:
            # held until the bucket recovers; the prebuilt item keeps its
            # original arrival/enqueued_at so WFQ aging credits the hold
            self._emit(Throttled(session_id, now, s.tenant,
                                 "queue", "submit"))
            self._throttled.setdefault(s.tenant, deque()).append(
                ("work", req)
            )
            return self._rid
        if stage == Stage.DEPRIORITIZE:
            self._emit(Throttled(session_id, now, s.tenant,
                                 "deprioritize", "submit"))
        self.pending.append(req)
        return self._rid

    # -- throttle release ----------------------------------------------------
    def _release_throttled(self, now: float) -> None:
        """Re-price each tenant's throttle buffer head against its (lazily
        refilled) bucket and release what it now covers.  FIFO *within* a
        tenant only — one flooding tenant's backlog never head-blocks
        another's.  Held opens re-price with ``queued=0``: the backlog
        bound sheds new arrivals, not work already accepted for holding."""
        for tenant, dq in self._throttled.items():
            while dq:
                entry = dq[0]
                if entry[0] == "open":
                    (_, sid, prompt, slo_class, draft_speed, extras,
                     t_request, queue_on_full) = entry
                    stage = self.tenants.admit_session(
                        tenant, len(prompt), now, queued=0)
                    if stage == Stage.QUEUE:
                        break
                    dq.popleft()
                    self._throttle_held.pop(sid, None)
                    if stage == Stage.REJECT:    # max_queued == 0 edge
                        self._rejected.add(sid)
                        self._emit(Rejected(sid, now, tenant))
                        continue
                    deprio = stage == Stage.DEPRIORITIZE
                    if deprio:
                        self._emit(Throttled(sid, now, tenant,
                                             "deprioritize", "open"))
                    self._admit_open(sid, prompt, slo_class, draft_speed,
                                     extras, t_request, queue_on_full,
                                     tenant, deprio)
                else:
                    req = entry[1]
                    stage = self.tenants.admit_block(
                        tenant, req.draft_len, now)
                    if stage == Stage.QUEUE:
                        break
                    dq.popleft()
                    self.tenants.get(tenant).tokens_in_flight += req.draft_len
                    req.deprioritized = stage == Stage.DEPRIORITIZE
                    if req.deprioritized:
                        self._emit(Throttled(req.session_id, now, tenant,
                                             "deprioritize", "submit"))
                    self.pending.append(req)

    # -- dispatch epoch -------------------------------------------------------
    def step(self, now: float, *, verify_time=None) -> list[Verdict]:
        """One dispatch epoch at time ``now``.

        Outcomes surface on the event stream (``VERDICT`` /
        ``FIRST_TOKEN`` / ``TTFT_RECORD`` / ``PREEMPTED`` events); the
        byte-identical verdict list is also *returned* as the legacy shim
        channel.

        ``verify_time``: optional callable mapping the list of served
        work items to the verification duration (seconds) to attribute
        to this epoch.  The event-driven cluster runtime passes one driven
        by the estimator (+ optional noise) so queueing/violation accounting
        runs on the virtual clock; by default each verdict carries the
        engine's measured wall time (synchronous CPU drivers).

        With a tracer the epoch is a ``wisp.step`` span whose children
        are ``wisp.admit``, ``wisp.schedule``, the engine's spans and
        ``wisp.apply``; each item served gets a ``wisp.queue`` interval
        from when it was queued to the step's start."""
        tr = self.tracer
        if tr is not None:
            tr.epoch += 1
            cold0 = self.engine.stats["cold_dispatches"]
        with span(tr, "wisp.step") as sp:
            verdicts = self._step(now, verify_time, tr)
            if tr is not None:
                served = self.last_served
                sp.set(ids=[_work_id(r) for r in served],
                       verify_rows=sum(r.kind == "verify" for r in served),
                       prefill_rows=sum(r.kind == "prefill" for r in served),
                       cold=self.engine.stats["cold_dispatches"] - cold0)
                for r in served:
                    tr.interval("wisp.queue", r.t_queued, sp.t0,
                                _work_id(r), kind=r.kind)
        return verdicts

    def _step(self, now, verify_time, tr) -> list[Verdict]:
        self.now = max(self.now, now)
        with span(tr, "wisp.admit") as sa:
            live0 = len(self.sessions) + len(self.prefilling)
            self._release_throttled(now)
            self._try_admit()
            # M(t_k): live free-page capacity, not a static config number
            self.memory_budget_tokens = (
                self.engine.memory_budget_tokens()
                if self.dynamic_memory_budget
                else self.sched_cfg.memory_budget_tokens
            )
            sa.set(admitted=len(self.sessions) + len(self.prefilling) - live0)
        self.last_served = []
        if not self.pending:
            return []
        with span(tr, "wisp.schedule", pending=len(self.pending)) as ss:
            decision = self.scheduler.schedule(
                self.pending, now,
                memory_budget_tokens=self.memory_budget_tokens
            )
            self.last_decision = decision
            ss.set(chosen=len(decision.batch))
            if not decision.batch:
                return []
            chosen = {r.req_id for r in decision.batch}
            self.pending = [r for r in self.pending if r.req_id not in chosen]
            items = [r.make_engine_item(self) for r in decision.batch]
        try:
            served = list(decision.batch)
            outcomes = self.engine.step(items)
        except OutOfPages:
            # The token budget over-admitted (committed tokens of sessions
            # outside the batch are not page headroom).  Shrink to whatever
            # fits — per-request execution — so the epoch still makes
            # progress instead of requeue-livelocking; requests that cannot
            # fit even alone go back to pending (they need a close_session
            # to free pages).
            served, outcomes = [], []
            for r, it in zip(decision.batch, items):
                try:
                    outcomes.extend(self.engine.step([it]))
                    served.append(r)
                except OutOfPages:
                    self.pending.append(r)
        with span(tr, "wisp.apply"):
            return self._apply(served, outcomes, now, verify_time)

    def _apply(self, served, outcomes, now, verify_time) -> list[Verdict]:
        """Requeue deferred work, then commit each outcome (verdicts,
        first tokens and their events)."""
        # work the engine deferred (e.g. prefill chunks the page pool could
        # not cover — state untouched) requeues like the OutOfPages verify
        # path above
        pairs, deferred = [], []
        for r, o in zip(served, outcomes):
            if r.deferred(o):
                deferred.append(r)
            else:
                pairs.append((r, o))
        if not pairs and deferred and len(self.prefilling) > 1:
            # Liveness: every chunk this epoch was uncoverable and nothing
            # else ran, so no future close/trim is coming from *this* pool
            # of work — partially-prefilled sessions are mutually blocking
            # (each holds pages the others need).  Preempt the
            # youngest-requested *prefilling session* (not merely the
            # youngest chunk scheduled this epoch — under memory pressure
            # the scheduler may have admitted only the oldest's chunk)
            # back to the admission queue: its pages are released, it
            # retries FIFO with its original TTFT clock, and the oldest
            # can finish.  Without this, N long prompts that each fit
            # alone but not together requeue forever.
            victim_sid = max(
                self.prefilling,
                key=lambda sid: (self.prefilling[sid].t_request, sid),
            )
            ps = self.prefilling.pop(victim_sid)
            deferred = [r for r in deferred if r.session_id != victim_sid]
            self.pending = [
                r for r in self.pending if r.session_id != victim_sid
            ]
            self.engine.abort_prefill(ps.state)
            self.admission_queue.push(
                (ps.session_id, [int(x) for x in ps.state.tokens],
                 ps.slo_class, ps.draft_speed, ps.state.extras,
                 ps.t_request, ps.tenant)
            )
            # keep the retry queue in request order (FIFO fairness)
            self.admission_queue.resort(key=lambda q: q[5])
            self.prefill_preemptions += 1
            self._emit(Preempted(victim_sid, now))
        self.pending.extend(deferred)
        self.last_served = [r for r, _ in pairs]

        dt_virtual = (
            None if verify_time is None else float(verify_time(self.last_served))
        )
        # epoch wall time: the verify batch and the ragged prefill pass run
        # back to back (all verify outcomes share one batch time, all chunk
        # outcomes share one pass time)
        wall = max((o.t_verify for _, o in pairs if hasattr(o, "t_verify")),
                   default=0.0) + \
            max((o.t_chunk for _, o in pairs if hasattr(o, "t_chunk")),
                default=0.0)
        self.last_verify_time = dt_virtual if dt_virtual is not None else wall
        #: verify hooks read this: None -> each verdict carries the engine's
        #: measured wall time; set -> the epoch's virtual duration
        self._dt_virtual = dt_virtual
        tv_epoch = self.last_verify_time

        verdicts = []
        for r, o in pairs:
            v = r.apply(self, o, now, tv_epoch)
            if v is not None:
                verdicts.append(v)
        return verdicts

    # -- work-item commit hooks (called via WorkItem.apply) -----------------
    def commit_verify(self, r, outcome, now: float, tv_epoch: float) -> Verdict:
        """Account one executed verification: EWMA acceptance update,
        committed-stream advance, deadline verdict (VERDICT event + the
        legacy return/log channels)."""
        s = self.sessions[r.session_id]
        if r.draft_len > 0:
            s.alpha = 0.8 * s.alpha + 0.2 * (outcome.accept_len / r.draft_len)
        s.rounds += 1
        s.committed_len += outcome.emitted
        tstate = self.tenants.get(s.tenant)
        tstate.tokens_in_flight = max(
            0, tstate.tokens_in_flight - r.draft_len)
        tstate.committed_tokens += outcome.emitted
        t_queue = max(0.0, now - r.enqueued_at)
        tv = outcome.t_verify if self._dt_virtual is None else self._dt_virtual
        complete = now + tv
        v = Verdict(
            session_id=r.session_id,
            accept_len=outcome.accept_len,
            token=outcome.token,
            emitted=outcome.emitted,
            t_queue=t_queue,
            t_verify=tv,
            deadline=r.deadline,
            violated=complete > r.deadline,
            round_index=r.round_index,
            queue_depth=len(self.pending),
        )
        self.log.append(v)
        self._last_verdict[r.session_id] = v
        self._emit(VerdictEvent(r.session_id, now, v))
        return v

    def apply_chunk(self, r, outcome, now: float, tv_epoch: float) -> None:
        """Account one executed prefill chunk: enqueue the successor chunk,
        or — on the final chunk — activate the session and surface its
        first token as a FIRST_TOKEN event (+ TTFT_RECORD)."""
        ps: PrefillingSession = r.payload
        st = ps.state
        if outcome.first_token is None:
            self._enqueue_chunk(ps, now)
            return
        del self.prefilling[ps.session_id]
        self._register(ps.session_id, st.slot, outcome.first_token,
                       st.total, ps.slo_class, ps.draft_speed,
                       tenant=ps.tenant)
        self.admitted.append((ps.session_id, outcome.first_token))
        self._emit(FirstToken(ps.session_id, now, outcome.first_token))
        t_first = now + tv_epoch
        rec = PrefillRecord(
            session_id=ps.session_id,
            prompt_len=st.total,
            chunks=st.chunks,
            t_request=ps.t_request,
            t_first=t_first,
            deadline=ps.deadline,
            violated=t_first > ps.deadline,
        )
        self.prefill_log.append(rec)
        self._emit(TTFTRecord(ps.session_id, now, rec))

    @property
    def queue_depth(self) -> int:
        return len(self.pending)

    @property
    def throttle_backlog(self) -> int:
        """Opens + verify blocks currently held by the tenant rate limiter.
        Dispatch gating must treat this as queued work: releases happen
        only inside ``step()``, so a throttled-only backlog still needs an
        epoch scheduled to drain."""
        return sum(len(dq) for dq in self._throttled.values())
