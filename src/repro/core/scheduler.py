"""SLO-aware verification batch scheduling (paper §4.2-4.3, Algorithm 1)
behind a pluggable policy registry.

Two first-class abstractions (docs/API.md):

**WorkItem** — one schedulable unit of server work.  The pool holds a
small class hierarchy behind one scheduling protocol (uniform
``deadline`` / ``goodput_value`` / ``batch_shape()`` plus engine hooks
``make_engine_item`` / ``apply``):

  * ``VerifyWork`` — a drafted block awaiting verification; the deadline
    is the SLO-class token-speed budget (Eq. 6/12);
  * ``PrefillChunkWork`` — one chunk of a cold prompt's prefill; the
    deadline is the session's TTFT deadline (DESIGN.md §8).

A future work type (e.g. a non-speculative decode fallback) is additive:
subclass ``WorkItem``, implement the four hooks, and every policy, the
estimator pricing, and the server's dispatch loop handle it unchanged.

**SchedulingPolicy** — the batch-selection rule, one per name in a
registry.  Per dispatch epoch t_k a policy selects a batch B_k under
(i) a GPU/TPU memory budget and (ii) its own ordering rule:

  * ``"wisp"`` (alias ``"slo"``) — Algorithm 1: EDF critical fast path
    past the Latest Start Time, utility-density best-effort fill, every
    admission validated by FeasibleAdd;
  * ``"fcfs"`` — SLED-style arrival order, fill to limits;
  * ``"edf"``  — earliest-deadline-first fill (deadline awareness
    without the estimator-driven criticality split);
  * ``"priority"`` — strict SLO-class priority, EDF within a class;
  * ``"wfq"`` (alias ``"fair"``) — weighted fair queueing over per-tenant
    virtual finish times with an SRPT bias and aging (no tenant starves;
    DESIGN.md §13).

This is host-side control logic (pure Python, no jax) — it runs on the
serving coordinator between device steps.  Both the functional server
(`repro.serving`) and the analytic simulator (`repro.sim`) select
policies from the same registry.
"""
from __future__ import annotations

import dataclasses
from typing import Iterable

from repro.core.estimator import BatchShape, EstimatorCoeffs


# ---------------------------------------------------------------------------
# Work items
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class WorkItem:
    """One pending work item on the server (the scheduling protocol).

    Subclasses define what the item *is* by overriding the pricing
    properties (``new_tokens``, ``goodput_value``) and the engine hooks
    (``make_engine_item``, ``apply``, ``deferred``); the scheduling
    fields are uniform so every `SchedulingPolicy` prices and orders any
    mix of kinds without branching.
    """

    req_id: int
    session_id: int
    slo_class: int               # index into class table
    arrival: float               # a_i (s)
    deadline: float              # d_i = a_i + tau_c (s); TTFT deadline for prefill
    draft_len: int = 0           # N_d (0 for non-verify work)
    cached_len: int = 0          # committed prefix length with valid KV
    alpha: float = 0.0           # expected acceptance rate of this session
    payload: object = None       # kind-specific (opaque to scheduling)
    #: verify: prefix tokens that must be re-prefilled because no KV is
    #: cached (cold start / cache eviction / SLED's no-cache baseline);
    #: prefill: the chunk length
    prefill_tokens: int = 0
    #: tokens whose KV currently sits in the host spill tier (DESIGN.md
    #: §12): part of ``cached_len`` for *memory* accounting (their pages
    #: re-enter the device pool on page-in), but an extra *time* cost —
    #: ``batch_shape()`` prices them like new tokens so a spilled
    #: session's verify is dearer than a resident one's and the
    #: utility-density fill prefers resident work under pressure
    pagein_tokens: int = 0
    # bookkeeping
    enqueued_at: float = 0.0
    round_index: int = 0
    # -- multi-tenant fields (DESIGN.md §13) ------------------------------
    #: owning tenant (the ``"wfq"`` policy buckets virtual time by this;
    #: every other policy ignores it)
    tenant: str = "default"
    #: the tenant's fair-share weight, stamped from the `TenantRegistry`
    #: at submit time (policies take a fixed (cfg, coeffs) constructor,
    #: so weights ride the items, not the policy)
    tenant_weight: float = 1.0
    #: the rate limiter borrowed from the tenant's debt band for this
    #: item — WFQ serves it at a fraction of the tenant's weight
    deprioritized: bool = False
    #: host clock (``time.perf_counter()``) when a tracing server queued
    #: the item: the start of its ``wisp.queue`` span; None untraced
    t_queued: float | None = None

    #: kind tag (class attribute, kept for observability and the legacy
    #: ``VerifyRequest(kind=...)`` constructor shim)
    kind = "work"

    # -- pricing (what every policy needs) --------------------------------
    @property
    def new_tokens(self) -> int:
        raise NotImplementedError

    @property
    def goodput_value(self) -> float:
        """g_hat: expected committed tokens if this item executes."""
        raise NotImplementedError

    def batch_shape(self) -> BatchShape:
        # pagein_tokens ride the new_tokens axis for TIME pricing only
        # (page-in moves whole pages across the host boundary, the same
        # bandwidth class as writing fresh KV); memory accounting keeps
        # using ``cached_len + new_tokens`` — the reloaded pages are the
        # cached tokens, already counted there
        return BatchShape(
            new_tokens=self.new_tokens + self.pagein_tokens,
            cached_tokens=self.cached_len,
        )

    # -- engine hooks (the serving coordinator protocol) ------------------
    def make_engine_item(self, server):
        """Build the engine-level item (`repro.serving.engine`) this work
        executes as.  ``server`` is the coordinator (duck-typed: session
        table, engine, determinism switches)."""
        raise NotImplementedError

    def apply(self, server, outcome, now: float, tv_epoch: float):
        """Commit one executed outcome back into the coordinator; returns
        a ``Verdict`` for verify-like work, ``None`` otherwise."""
        raise NotImplementedError

    def deferred(self, outcome) -> bool:
        """True when ``outcome`` means "could not run, requeue me" (e.g. a
        prefill chunk the page pool could not cover this epoch)."""
        return False


@dataclasses.dataclass
class VerifyWork(WorkItem):
    """A drafted block awaiting verification (``payload`` = (draft token
    ids, dense q logits | None, `CompactQ` | None) — exactly one q
    representation is set unless the verifier is greedy, which reads
    neither).  Deadline is the SLO-class token-speed budget."""

    kind = "verify"

    @property
    def new_tokens(self) -> int:
        # + the re-fed last committed token + any uncached prefix
        return self.draft_len + 1 + self.prefill_tokens

    @property
    def goodput_value(self) -> float:
        """Expected committed tokens (paper Eq. 5, + bonus token)."""
        return self.alpha * self.draft_len + 1.0

    def make_engine_item(self, server):
        from repro.serving.engine import VerifyItem

        s = server.sessions[self.session_id]
        toks, qlog, qc = (self.payload if len(self.payload) == 3
                          else (*self.payload, None))
        return VerifyItem(
            slot=s.slot, draft_tokens=toks, q_logits=qlog, q_compact=qc,
            rng_tag=(self.session_id, self.cached_len)
            if server.deterministic_verify else None,
        )

    def apply(self, server, outcome, now, tv_epoch):
        return server.commit_verify(self, outcome, now, tv_epoch)


@dataclasses.dataclass
class PrefillChunkWork(WorkItem):
    """One chunk of a cold prompt's prefill (``payload`` = the server's
    PrefillingSession; ``prefill_tokens`` = chunk length; ``cached_len``
    = prompt prefix already prefilled or prefix-cache-covered).

    Every chunk of a session carries the session's **TTFT deadline**.
    Chunks are usually best-effort fill; as the TTFT deadline nears, LST
    promotes the remaining chunks to the critical fast path like any
    verify request.  g_hat is 1.0 — a prefill commits at most the
    session's first token — so long prompts get a low utility density
    and fill spare capacity instead of outbidding verification, exactly
    the paper's interference suppression (DESIGN.md §8)."""

    kind = "prefill"

    @property
    def new_tokens(self) -> int:
        # a chunk feeds exactly its prompt tokens (no draft block, no
        # re-fed last-committed token — the session has none yet)
        return self.prefill_tokens

    @property
    def goodput_value(self) -> float:
        return 1.0

    def make_engine_item(self, server):
        from repro.serving.engine import PrefillChunkItem

        return PrefillChunkItem(self.payload.state, self.prefill_tokens)

    def apply(self, server, outcome, now, tv_epoch):
        server.apply_chunk(self, outcome, now, tv_epoch)
        return None

    def deferred(self, outcome) -> bool:
        return bool(outcome.oom)


#: kind tag -> concrete work class (extended by new work types)
WORK_KINDS: dict[str, type] = {
    VerifyWork.kind: VerifyWork,
    PrefillChunkWork.kind: PrefillChunkWork,
}


def VerifyRequest(*args, kind: str = "verify", **kwargs) -> WorkItem:
    """Deprecated constructor shim: the stringly-typed
    ``VerifyRequest(kind=...)`` now dispatches to the `WorkItem` class
    hierarchy (``VerifyWork`` / ``PrefillChunkWork``).  Field names and
    order are unchanged; new code should construct the classes directly."""
    try:
        cls = WORK_KINDS[kind]
    except KeyError:
        raise ValueError(
            f"unknown work kind {kind!r}; registered: {sorted(WORK_KINDS)}"
        ) from None
    return cls(*args, **kwargs)


@dataclasses.dataclass
class SchedulerConfig:
    #: KV-token budget M(t_k).  A static default for standalone use; the
    #: serving coordinator overrides it per dispatch epoch (via
    #: ``schedule(..., memory_budget_tokens=...)``) from the verification
    #: engine's live page-allocator state (free + evictable pages), so
    #: admission tracks real cache pressure, not a constant.
    memory_budget_tokens: int = 1 << 20
    guard_time: float = 0.005             # delta (s)
    #: how long before LST a request enters the critical fast path.  The
    #: paper's "t >= LST_i" alone leaves a zero-width window between
    #: "critical" and "already hopeless"; opening the window eta early is
    #: what makes the EDF fast path actually fire.
    criticality_window: float = 0.020
    max_batch_requests: int = 64
    kv_bytes_per_token: int = 0           # informational


@dataclasses.dataclass
class ScheduleDecision:
    batch: list        # [WorkItem]
    est_time: float    # T_hat(B_k)
    critical: int      # how many came from the critical fast path
    skipped_infeasible: int
    epoch: float
    #: the budget this epoch was admitted against (observability: dynamic
    #: budgets change per epoch with cache pressure)
    memory_budget_tokens: int = 0
    #: registry name of the policy that produced this decision
    policy: str = ""


# ---------------------------------------------------------------------------
# Policy registry
# ---------------------------------------------------------------------------
#: registry name (and aliases) -> policy class
POLICIES: dict[str, type] = {}


def register_policy(name: str, *aliases: str):
    """Class decorator: register a `SchedulingPolicy` under ``name`` (its
    canonical ``cls.name``) plus any legacy aliases."""

    def deco(cls):
        cls.name = name
        for n in (name, *aliases):
            POLICIES[n] = cls
        return cls

    return deco


def available_policies() -> list[str]:
    """Canonical registered policy names, sorted."""
    return sorted({cls.name for cls in POLICIES.values()})


def make_policy(policy, cfg: SchedulerConfig, coeffs: EstimatorCoeffs):
    """Resolve ``policy`` — a registry name (``"wisp"``, ``"fcfs"``,
    ``"edf"``, ``"priority"``; legacy alias ``"slo"``), a policy class,
    or an already-built instance — into a policy instance."""
    if isinstance(policy, str):
        try:
            cls = POLICIES[policy]
        except KeyError:
            raise ValueError(
                f"unknown scheduling policy {policy!r}; registered: "
                f"{available_policies()}"
            ) from None
        return cls(cfg, coeffs)
    if isinstance(policy, type):
        return policy(cfg, coeffs)
    return policy


class SchedulingPolicy:
    """Batch-selection protocol + the pricing helpers every rule shares.

    ``schedule(pending, t_k, *, memory_budget_tokens=None) ->
    ScheduleDecision`` must (a) draw its batch from ``pending`` without
    duplicates, (b) keep ``memory_tokens(batch)`` within the budget and
    ``len(batch)`` within ``cfg.max_batch_requests``, and (c) report the
    estimator's batch time as ``est_time``.  ``memory_budget_tokens``
    overrides the static config budget for one epoch (the coordinator
    passes the engine's live free-page capacity here)."""

    name = "?"

    def __init__(self, cfg: SchedulerConfig, coeffs: EstimatorCoeffs):
        self.cfg = cfg
        self.coeffs = coeffs

    # -- shared pricing ----------------------------------------------------
    def batch_time(self, batch: Iterable[WorkItem]) -> float:
        shapes = [r.batch_shape() for r in batch]
        if not shapes:
            return 0.0
        return self.coeffs.predict(shapes)

    def memory_tokens(self, batch: Iterable[WorkItem]) -> int:
        return sum(r.cached_len + r.new_tokens for r in batch)

    def _budget(self, memory_budget_tokens: int | None) -> int:
        return (
            self.cfg.memory_budget_tokens
            if memory_budget_tokens is None
            else memory_budget_tokens
        )

    def schedule(
        self, pending: list, t_k: float, *,
        memory_budget_tokens: int | None = None,
    ) -> ScheduleDecision:
        raise NotImplementedError

    def _decision(self, batch, t_k, budget, *, critical=0, skipped=0):
        return ScheduleDecision(
            batch=batch,
            est_time=self.batch_time(batch),
            critical=critical,
            skipped_infeasible=skipped,
            epoch=t_k,
            memory_budget_tokens=budget,
            policy=self.name,
        )

    def _fill_in_order(self, pending, t_k, budget, key) -> ScheduleDecision:
        """Greedy fill in ``key`` order under the memory/batch caps —
        the shared body of the strict-order baselines (EDF, priority):
        no estimator feasibility check, no smaller-item bypass past the
        first one that does not fit."""
        batch: list = []
        tokens = 0
        skipped = 0
        for r in sorted(pending, key=key):
            if len(batch) >= self.cfg.max_batch_requests:
                break
            need = r.cached_len + r.new_tokens
            if tokens + need > budget:
                skipped += 1
                break
            batch.append(r)
            tokens += need
        return self._decision(batch, t_k, budget, skipped=skipped)


@register_policy("wisp", "slo")
class SLOScheduler(SchedulingPolicy):
    """Algorithm 1: EDF critical fast path + utility-density fill, every
    admission validated by FeasibleAdd against the estimator."""

    # -- per-request estimates -------------------------------------------
    def v_hat(self, r: WorkItem) -> float:
        """Marginal verification cost of r alone (used for U_i and LST_i)."""
        return self.coeffs.predict([r.batch_shape()])

    def utility(self, r: WorkItem) -> float:
        return r.goodput_value / max(self.v_hat(r), 1e-9)

    def lst(self, r: WorkItem) -> float:
        return r.deadline - self.v_hat(r) - self.cfg.guard_time

    # -- batch feasibility (FeasibleAdd) ----------------------------------
    def feasible_add(
        self, batch, r, t_k, doomed: set | None = None,
        memory_budget_tokens: int | None = None,
    ) -> bool:
        """FeasibleAdd (Alg. 1): memory + earliest *winnable* deadline vs
        estimated batch completion.  Requests in ``doomed`` have already
        missed their deadline — Eq. 15 cannot bind for them (they violate
        regardless), so they do not constrain d_min; excluding them avoids
        the one-request death-spiral a literal reading would cause."""
        budget = self._budget(memory_budget_tokens)
        nb = batch + [r]
        if len(nb) > self.cfg.max_batch_requests:
            return False
        if self.memory_tokens(nb) > budget:
            return False
        doomed = doomed or set()
        winnable = [x.deadline for x in nb if x.req_id not in doomed]
        if not winnable:
            return True
        return t_k + self.batch_time(nb) + self.cfg.guard_time <= min(winnable)

    # -- Algorithm 1 -------------------------------------------------------
    def schedule(
        self, pending: list, t_k: float, *,
        memory_budget_tokens: int | None = None,
    ) -> ScheduleDecision:
        budget = self._budget(memory_budget_tokens)
        # Requests that cannot meet their deadline even alone are "doomed":
        # they violate regardless of what we do, so they must not block the
        # critical fast path (a literal Alg. 1 would dispatch them one at a
        # time and death-spiral the verifier).  They join the best-effort
        # fill — served promptly, batched efficiently, violation recorded.
        v_hats = {r.req_id: self.v_hat(r) for r in pending}
        doomed = {
            r.req_id
            for r in pending
            if t_k + v_hats[r.req_id] > r.deadline      # missed even solo
        }
        crit = [
            r for r in pending
            if r.req_id not in doomed
            and t_k >= (r.deadline - v_hats[r.req_id] - self.cfg.guard_time
                        - self.cfg.criticality_window)
        ]
        non = [r for r in pending if r not in crit]
        crit.sort(key=lambda r: r.deadline)                 # EDF
        non.sort(key=lambda r: -self.utility(r))            # utility density

        batch: list = []
        skipped = 0
        stop = False
        for r in crit:
            if self.feasible_add(batch, r, t_k, doomed,
                                 memory_budget_tokens=budget):
                batch.append(r)
            else:
                stop = True
                skipped += 1
                break
        n_crit = len(batch)
        if not stop:
            for r in non:
                if self.feasible_add(batch, r, t_k, doomed,
                                     memory_budget_tokens=budget):
                    batch.append(r)
                else:
                    skipped += 1
                    break
        return self._decision(batch, t_k, budget, critical=n_crit,
                              skipped=skipped)


@register_policy("fcfs")
class FCFSScheduler(SchedulingPolicy):
    """SLED-style baseline: first-come-first-served, fill to limits, no
    deadline awareness."""

    def schedule(
        self, pending: list, t_k: float, *,
        memory_budget_tokens: int | None = None,
    ) -> ScheduleDecision:
        budget = self._budget(memory_budget_tokens)
        batch: list = []
        for r in sorted(pending, key=lambda x: x.arrival):
            if len(batch) >= self.cfg.max_batch_requests:
                break
            if self.memory_tokens(batch + [r]) > budget:
                break
            batch.append(r)
        return self._decision(batch, t_k, budget)


@register_policy("edf")
class EDFScheduler(SchedulingPolicy):
    """Earliest-deadline-first baseline: admit in deadline order, fill to
    the memory/batch caps.

    Deadline-aware but estimator-blind: no Latest-Start-Time criticality
    split, no utility-density fill, no FeasibleAdd completion check — so
    a batch may still blow the earliest deadline it contains.  Isolates
    how much of WISP's win comes from mere deadline *ordering* vs from
    Algorithm 1's estimator-validated admission."""

    def schedule(
        self, pending: list, t_k: float, *,
        memory_budget_tokens: int | None = None,
    ) -> ScheduleDecision:
        return self._fill_in_order(
            pending, t_k, self._budget(memory_budget_tokens),
            key=lambda x: (x.deadline, x.arrival, x.req_id),
        )


@register_policy("priority")
class PriorityScheduler(SchedulingPolicy):
    """Strict SLO-class priority: premium classes (lower class index =
    faster token-speed promise) always outrank best-effort ones; EDF
    order within a class; fill to the memory/batch caps.

    The classic starvation-prone baseline — a saturated premium tier
    locks lower tiers out entirely, which is exactly the failure mode
    WISP's utility-density fill avoids."""

    def schedule(
        self, pending: list, t_k: float, *,
        memory_budget_tokens: int | None = None,
    ) -> ScheduleDecision:
        return self._fill_in_order(
            pending, t_k, self._budget(memory_budget_tokens),
            key=lambda x: (x.slo_class, x.deadline, x.arrival, x.req_id),
        )


@register_policy("wfq", "fair")
class WFQScheduler(SchedulingPolicy):
    """Weighted fair queueing over per-tenant virtual finish times, with
    an SRPT bias and aging (DESIGN.md §13).

    Each item's cost is its token footprint; its virtual finish time is

        vfinish = max(V, vt[tenant]) + cost / w_eff

    where ``V`` is the global virtual clock, ``vt[tenant]`` the tenant's
    last virtual finish, and ``w_eff`` the tenant's weight (cut to
    ``deprio_factor`` of itself for items the rate limiter borrowed from
    the debt band).  Items are admitted in order of

        vfinish + srpt_bias * cost - aging_rate * wait

    so short items edge ahead within a fair share (SRPT) and long-waiting
    items climb monotonically (aging: an item backlogged ``t`` seconds
    gains ``aging_rate * t`` of virtual-time credit, which bounds any
    backlogged tenant's wait — no tenant starves).  Kind-agnostic like
    every policy: verify and prefill work compete in one order.

    Virtual-time state lives on the policy instance and persists across
    epochs; after each selection the tenant clocks advance by the served
    cost over weight and the global clock jumps to the smallest
    backlogged tenant clock (standard virtual-time tracking — an idle
    tenant does not bank credit forever).
    """

    #: cost multiplier favoring short items within a fair share
    srpt_bias = 0.5
    #: virtual-time credit per real second of queueing wait
    aging_rate = 1.0
    #: weight multiplier for debt-band (deprioritized) items
    deprio_factor = 0.25

    def __init__(self, cfg: SchedulerConfig, coeffs: EstimatorCoeffs):
        super().__init__(cfg, coeffs)
        self.vtime = 0.0
        self.tenant_vt: dict[str, float] = {}

    @staticmethod
    def _cost(r: WorkItem) -> float:
        # token footprint (same axis the memory budget is charged in);
        # normalized so typical blocks are O(1e-2) virtual seconds and the
        # aging credit (1 vt/s real) can actually overtake them
        return (r.cached_len + r.new_tokens) / 1024.0

    def _weight(self, r: WorkItem) -> float:
        w = r.tenant_weight * (self.deprio_factor if r.deprioritized else 1.0)
        return max(w, 1e-6)

    def schedule(
        self, pending: list, t_k: float, *,
        memory_budget_tokens: int | None = None,
    ) -> ScheduleDecision:
        budget = self._budget(memory_budget_tokens)

        def vfinish(r: WorkItem) -> float:
            start = max(self.vtime, self.tenant_vt.get(r.tenant, 0.0))
            return start + self._cost(r) / self._weight(r)

        def key(r: WorkItem):
            wait = max(t_k - r.enqueued_at, 0.0)
            return (
                vfinish(r) + self.srpt_bias * self._cost(r)
                - self.aging_rate * wait,
                r.deadline,
                r.req_id,
            )

        decision = self._fill_in_order(pending, t_k, budget, key=key)
        # advance virtual time for the work actually served
        for r in decision.batch:
            start = max(self.vtime, self.tenant_vt.get(r.tenant, 0.0))
            self.tenant_vt[r.tenant] = start + self._cost(r) / self._weight(r)
        served = {r.req_id for r in decision.batch}
        backlog_vt = [
            self.tenant_vt.get(r.tenant, 0.0)
            for r in pending if r.req_id not in served
        ]
        if backlog_vt:
            self.vtime = max(self.vtime, min(backlog_vt))
        elif self.tenant_vt:
            self.vtime = max(self.vtime, max(self.tenant_vt.values()))
        return decision
