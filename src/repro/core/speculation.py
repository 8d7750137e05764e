"""Per-session adaptive speculation control: dynamic draft length K.

The paper's "intelligent speculation controller" (§4.1) has two halves.
`core/controller.py` implements the *within-block* half — stop drafting
at the first predicted rejection.  This module implements the
*between-block* half: choose the next block's draft-length cap K from
the session's measured signals, so K tracks device/link heterogeneity
instead of being one static constant per run (SpecEdge's observation:
the edge-assisted goodput win lives in adapting K).

A `SpeculationController` is per-session edge-side state behind a
registry (mirroring the `SchedulingPolicy` registry in
`core/scheduler.py`).  The drive loop is::

    k = ctl.next_k()                       # cap for the next block
    ... draft (<= k tokens), submit, await verdict ...
    ctl.observe(accept_len=.., k_used=.., rtt=.., queue_depth=..)

Signals the adaptive law consumes, all EWMA-smoothed:

  * **acceptance** — the measured accept fraction of each verified
    block, or the predictor's calibrated per-token accept probability
    when one rides along (``p_accept``);
  * **round-trip time** — draft uplink + verdict downlink: a long link
    amortizes more drafting per round (the per-round fixed cost is paid
    either way);
  * **verifier load** — the server's pending-pool depth piggybacked on
    each verdict (`Verdict.queue_depth`): a saturated verifier rejects
    deep blocks' tail tokens anyway (batch slots are contended), so
    back off K and cut Wasted Drafting Time.

The chosen K is always clamped to ``[1, k_max]`` and slew-limited to
one step per observation (hysteresis) so K never thrashes on noise.

Determinism note (DESIGN.md §11): block boundaries feed the
verification rng keys ``(session_id, committed_len)``, so an adaptive
run's streams lawfully differ from a static-K run's — but each is a
pure function of its config, and equals a solo lock-step replay of the
same per-round K schedule (`serving/oracle.py`, the committed-prefix
oracle the `benchmarks/adaptive_k.py` gate checks byte-for-byte).
"""
from __future__ import annotations

import math

#: bound on each float term of the adaptive K target before int(), far
#: past any k_max
_TERM_CAP = 2.0**31

# ---------------------------------------------------------------------------
# Registry (same shape as core/scheduler.py's policy registry)
# ---------------------------------------------------------------------------

SPEC_POLICIES: dict[str, type] = {}


def register_spec_policy(name: str, *aliases: str):
    """Class decorator: register a `SpeculationController` under ``name``
    (and aliases).  Sets ``cls.name`` to the canonical name."""

    def deco(cls):
        cls.name = name
        for n in (name, *aliases):
            SPEC_POLICIES[n] = cls
        return cls

    return deco


def available_spec_policies() -> list[str]:
    """Canonical registered names, sorted (aliases excluded)."""
    return sorted({cls.name for cls in SPEC_POLICIES.values()})


def make_spec_controller(policy="static", *, k_max: int = 8,
                         draft_speed: float = 50.0, predictor=None,
                         **kwargs) -> "SpeculationController":
    """Resolve ``policy`` (name, class, or ready instance) into a
    controller bound to one session's parameters."""
    if isinstance(policy, SpeculationController):
        return policy
    if isinstance(policy, type) and issubclass(policy, SpeculationController):
        cls = policy
    else:
        try:
            cls = SPEC_POLICIES[policy]
        except KeyError:
            raise ValueError(
                f"unknown speculation policy {policy!r}; "
                f"available: {available_spec_policies()}"
            ) from None
    return cls(k_max=k_max, draft_speed=draft_speed, predictor=predictor,
               **kwargs)


# ---------------------------------------------------------------------------
# Base + policies
# ---------------------------------------------------------------------------


class SpeculationController:
    """Chooses each block's draft-length cap for ONE session stream.

    Besides the per-policy ``next_k`` law, every controller carries the
    **link-health half of graceful degradation** (DESIGN.md §14): the
    driver reports each round's link outcome via ``observe_link`` (ok =
    a verdict landed; not-ok = a retry timeout; ``down`` = the runtime
    declared the link down after ``link_down_after`` consecutive
    timeouts), the controller EWMA-smooths it into ``link_health`` and,
    when ``degrade`` is enabled, ``choose_k`` shrinks the policy's K
    under flap and falls back to K=1 (one draft token per round — the
    server-side-decode floor) while the link is down.  Recovery is
    hysteretic: the down latch clears only after ``recover_streak``
    consecutive ok rounds AND health back above ``recover_above``, so K
    never thrashes across a flapping boundary.  With ``degrade`` off
    (the default) ``choose_k`` is exactly ``next_k`` — static-policy
    streams stay byte-identical to the fault-free run."""

    name = "base"

    def __init__(self, *, k_max: int = 8, draft_speed: float = 50.0,
                 predictor=None, degrade: bool = False,
                 link_ema: float = 0.35, degrade_below: float = 0.7,
                 recover_above: float = 0.9, recover_streak: int = 2, **_):
        self.k_max = max(1, int(k_max))
        self.draft_speed = float(draft_speed)
        self.predictor = predictor
        # -- link-health degradation law (DESIGN.md §14) -------------------
        self.degrade = bool(degrade)
        self.link_ema = float(link_ema)
        self.degrade_below = float(degrade_below)
        self.recover_above = float(recover_above)
        self.recover_streak = max(1, int(recover_streak))
        self.link_health = 1.0
        self.link_down = False
        self._ok_streak = 0
        #: the most recent ``choose_k`` shrank K below the policy's pick
        #: (or pinned the K=1 down-mode floor) — the runtime's
        #: degraded-round counter reads this
        self.degraded_last = False

    def start_session(self) -> None:
        """Reset any per-stream state (a device reuses its controller
        across churned sessions).  Link health deliberately survives —
        it is a property of the device's LINK, not of one session."""

    def next_k(self) -> int:
        """Draft-length cap for the next block, in ``[1, k_max]``."""
        raise NotImplementedError

    # -- link health + graceful degradation (DESIGN.md §14) ----------------
    def observe_link(self, ok: bool, *, down: bool = False) -> None:
        """Feed one link outcome: ``ok`` when a verdict reached the
        device, not-ok when a round timed out.  ``down=True`` latches
        hard-down mode (the runtime asserts it after
        ``link_down_after`` consecutive timeouts)."""
        self.link_health = ((1.0 - self.link_ema) * self.link_health
                            + self.link_ema * (1.0 if ok else 0.0))
        if ok:
            self._ok_streak += 1
            if (self.link_down and self._ok_streak >= self.recover_streak
                    and self.link_health >= self.recover_above):
                self.link_down = False
        else:
            self._ok_streak = 0
            if down:
                self.link_down = True

    def choose_k(self) -> int:
        """The policy's ``next_k``, degraded by link health when enabled:
        K=1 while the link is down (server-side decode — one draft token
        still carries the round, the verifier's bonus token does the
        committing), K scaled by the health EWMA under flap.  Identical
        to ``next_k`` when ``degrade`` is off."""
        k = self.next_k()
        self.degraded_last = False
        if not self.degrade:
            return k
        if self.link_down:
            self.degraded_last = True
            return 1
        if self.link_health < self.degrade_below:
            shrunk = max(1, min(k, int(math.ceil(k * self.link_health))))
            self.degraded_last = shrunk < k
            return shrunk
        return k

    def observe(self, *, accept_len: int = 0, k_used: int = 0,
                p_accept: float | None = None, rtt: float | None = None,
                queue_depth: float | None = None) -> None:
        """Feed back one verified round's signals (all optional — a
        driver reports what it measures)."""

    # -- migration (fleet failover carries controller state along) ---------
    def state(self) -> dict:
        """Serializable per-session state for migration hand-off."""
        return {}

    def load_state(self, state: dict) -> None:
        pass


@register_spec_policy("static", "fixed")
class StaticSpecController(SpeculationController):
    """The pre-adaptive behavior: every block gets the full ``k_max``
    budget (within-block early stop still applies via the predictor)."""

    def next_k(self) -> int:
        return self.k_max


@register_spec_policy("scripted", "schedule")
class ScriptedSpecController(SpeculationController):
    """Replay a fixed per-block K schedule — the committed-prefix
    oracle's controller (`serving/oracle.py`) and a test fixture.
    Past the schedule's end the last entry holds."""

    def __init__(self, *, schedule=(), k_max: int = 8, **kw):
        super().__init__(k_max=k_max, **kw)
        self.schedule = [int(k) for k in schedule]
        self._i = 0

    def start_session(self) -> None:
        self._i = 0

    def next_k(self) -> int:
        if not self.schedule:
            return self.k_max
        k = self.schedule[min(self._i, len(self.schedule) - 1)]
        self._i += 1
        return max(1, min(int(k), self.k_max))

    def state(self) -> dict:
        return {"i": self._i}

    def load_state(self, state: dict) -> None:
        self._i = int(state.get("i", 0))


@register_spec_policy("adaptive", "dynamic")
class AdaptiveSpecController(SpeculationController):
    """The control law (DESIGN.md §11).  Per verified block, with
    EWMA-smoothed acceptance ``p``, RTT ``r`` and verifier queue depth
    ``q``::

        k_p     = max k with p^k >= gain_floor      (acceptance term)
        k_rtt   = round(r * draft_speed * rtt_gain) (link-amortization)
        k_load  = floor(q / load_scale)             (congestion brake)
        target  = clamp(k_p + k_rtt - k_load, 1, k_max)
        k      <- k + sign(target - k)              (slew-limit: hysteresis)

    Intuition: ``p^k`` is the probability a depth-k block fully accepts;
    drafting past the depth where that falls under ``gain_floor`` is
    expected waste (Wasted Drafting Time, Eq. 7-8).  A slow link raises
    the fixed per-round cost, so deeper blocks amortize it (SpecEdge);
    a deep verifier queue means extra drafted tokens mostly wait to be
    rejected, so back off.  The one-step slew limit plus EWMA smoothing
    is the hysteresis that keeps K from thrashing between rounds.
    """

    def __init__(self, *, k_max: int = 8, draft_speed: float = 50.0,
                 predictor=None, alpha0: float = 0.6, ema: float = 0.3,
                 gain_floor: float = 0.35, rtt_gain: float = 0.5,
                 load_scale: float = 4.0, k0: int | None = None, **kw):
        super().__init__(k_max=k_max, draft_speed=draft_speed,
                         predictor=predictor, **kw)
        self.alpha0 = float(alpha0)
        self.ema = float(ema)
        self.gain_floor = float(gain_floor)
        self.rtt_gain = float(rtt_gain)
        self.load_scale = max(1e-6, float(load_scale))
        self._k0 = self.k_max if k0 is None else max(1, min(int(k0), self.k_max))
        self.start_session()

    def start_session(self) -> None:
        self.alpha = self.alpha0
        self.rtt = 0.0
        self.load = 0.0
        self.k = self._k0

    def _ewma(self, old: float, new: float) -> float:
        return (1.0 - self.ema) * old + self.ema * new

    def observe(self, *, accept_len: int = 0, k_used: int = 0,
                p_accept: float | None = None, rtt: float | None = None,
                queue_depth: float | None = None) -> None:
        # acceptance: prefer the predictor's calibrated estimate when the
        # driver passes one; fall back to the measured accept fraction
        if p_accept is not None and math.isfinite(p_accept):
            self.alpha = self._ewma(self.alpha, min(max(p_accept, 0.0), 1.0))
        elif k_used > 0:
            frac = min(max(accept_len / k_used, 0.0), 1.0)
            self.alpha = self._ewma(self.alpha, frac)
        if rtt is not None and math.isfinite(rtt) and rtt >= 0.0:
            self.rtt = self._ewma(self.rtt, rtt)
        if queue_depth is not None and math.isfinite(queue_depth) \
                and queue_depth >= 0.0:
            self.load = self._ewma(self.load, queue_depth)
        self.k = self._step_towards(self._target())

    def _target(self) -> int:
        p = min(max(self.alpha, 0.05), 0.95)
        # largest k with p^k >= gain_floor  <=>  k <= ln(floor)/ln(p)
        k_p = int(math.log(self.gain_floor) / math.log(p))
        # each term saturates at _TERM_CAP before int(): a huge but finite
        # rtt or queue depth can overflow the float product to inf
        k_rtt = int(round(min(self.rtt * self.draft_speed * self.rtt_gain,
                              _TERM_CAP)))
        k_load = int(min(self.load / self.load_scale, _TERM_CAP))
        return max(1, min(k_p + k_rtt - k_load, self.k_max))

    def _step_towards(self, target: int) -> int:
        if target > self.k:
            return min(self.k + 1, self.k_max)
        if target < self.k:
            return max(self.k - 1, 1)
        return self.k

    def next_k(self) -> int:
        return max(1, min(self.k, self.k_max))

    def state(self) -> dict:
        return {"alpha": self.alpha, "rtt": self.rtt, "load": self.load,
                "k": self.k}

    def load_state(self, state: dict) -> None:
        self.alpha = float(state.get("alpha", self.alpha0))
        self.rtt = float(state.get("rtt", 0.0))
        self.load = float(state.get("load", 0.0))
        self.k = max(1, min(int(state.get("k", self._k0)), self.k_max))
