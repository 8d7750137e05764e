"""The one traffic generator: a mix file of parameters -> a session plan.

Every seed gets the same multiset of sizes, edge profiles and (unit-rate)
inter-arrival gaps, drawn from the mix's own ``base_seed``; ``--seed``
permutes their order and draws every token.  So runs with different seeds
do the same work in another order, and the same seed gives the same plan.

Arrivals are open loop: a Poisson process at ``rate_per_s``, optionally
modulated by periodic bursts (``factor`` times the base rate for
``length_s`` of every ``period_s``), conditioned on its expected count in
each part of the run (pre-roll, window, drain), so that every seed's
window carries the same number of sessions of the same sizes.
Prompt parts are whole multiples of ``len_quantum`` tokens.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


def load_mix(name: str, directory: Path = TRAFFIC_DIR) -> dict:
    path = Path(directory) / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} at {path}")
    return json.loads(path.read_text())


@dataclasses.dataclass
class SessionPlan:
    sid: int
    due: float                 # arrival at the server, s from the run's t0
    prefix: int                # index of the shared prompt prefix
    suffix_len: int
    response_len: int
    edge: dict                 # draft_tok_s, rtt_s, slo_class


def _rng(*words) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence(
        [int(w) & 0xFFFFFFFF for w in words] + [int(w) >> 32 for w in words]))


def _qround(n, q: int, lo: int, hi: int) -> int:
    return int(np.clip(-(-int(n) // q) * q, lo, hi))


def _lognormal(rng, spec: dict, n: int) -> np.ndarray:
    return np.exp(np.log(spec["median"]) + spec["sigma"] * rng.standard_normal(n))


def _zipf_choice(rng, count: int, s: float, n: int) -> np.ndarray:
    w = 1.0 / np.arange(1, count + 1) ** s
    return rng.choice(count, size=n, p=w / w.sum())


def prefix_lengths(mix: dict) -> list[int]:
    p, q = mix["prefixes"], mix["len_quantum"]
    rng = _rng(mix["base_seed"], 1)
    raw = np.exp(rng.uniform(np.log(p["min"]), np.log(p["max"]), p["count"]))
    return [_qround(x, q, p["min"], p["max"]) for x in raw]


def cumulative(t, mix: dict):
    """Expected arrivals by time ``t``: the Poisson rate, with bursts of
    ``factor`` times it for ``length_s`` of every ``period_s``."""
    rate, b = mix["rate_per_s"], mix.get("bursts")
    t = np.asarray(t, float)
    if not b:
        return rate * t
    hi, L, P = b["factor"] * rate, b["length_s"], b["period_s"]
    cycles = np.floor(t / P)
    r = np.clip(t - cycles * P, 0.0, P)
    per_cycle = hi * L + rate * (P - L)
    return cycles * per_cycle + np.where(r < L, hi * r, hi * L + rate * (r - L))


def inverse(u, mix: dict):
    """The time by which ``u`` arrivals are expected (inverse of
    `cumulative`)."""
    rate, b = mix["rate_per_s"], mix.get("bursts")
    u = np.asarray(u, float)
    if not b:
        return u / rate
    hi, L, P = b["factor"] * rate, b["length_s"], b["period_s"]
    per_cycle = hi * L + rate * (P - L)
    cycles = np.floor(u / per_cycle)
    r = np.clip(u - cycles * per_cycle, 0.0, per_cycle)
    return cycles * P + np.where(r < hi * L, r / hi, L + (r - hi * L) / rate)


def sessions(mix: dict, seed: int, n: int, segment: int = 0
             ) -> list[SessionPlan]:
    """``n`` sessions of the mix's multiset for ``segment``, in ``seed``'s
    order (``sid`` and ``due`` are set by `plan`)."""
    base = _rng(mix["base_seed"], 2, segment, n)
    q = mix["len_quantum"]
    s, r = mix["suffix"], mix["response"]
    pre = prefix_lengths(mix)
    prefix = _zipf_choice(base, len(pre), mix["prefixes"]["zipf_s"], n)
    suffix = [_qround(x, q, s["min"], s["max"]) for x in _lognormal(base, s, n)]
    resp = np.clip(np.round(_lognormal(base, r, n)), r["min"], r["max"])
    edges = mix["edges"]
    k1 = mix["k"] + 1
    out = []
    for j in _rng(seed, 3, segment).permutation(n):
        room = mix["max_len"] - pre[prefix[j]] - suffix[j] - k1
        out.append(SessionPlan(
            sid=-1, due=0.0, prefix=int(prefix[j]), suffix_len=suffix[j],
            response_len=int(min(resp[j], room)), edge=edges[j % len(edges)]))
    return out


def plan(mix: dict, seed: int, seconds: float, rate: float | None = None
         ) -> list[SessionPlan]:
    """The run's sessions, open loop: the pre-roll, the window and the
    drain each get as many arrivals as their expected count, with the
    same sizes and the same (unit) gaps for every seed, in ``seed``'s
    order.  So each seed's window holds the same work."""
    if rate is not None:
        mix = dict(mix, rate_per_s=rate)
    pre, drain = mix["preroll_s"], mix["drain_s"]
    bounds = [0.0, pre, pre + seconds, pre + seconds + drain]
    out = []
    for k, (a, b) in enumerate(zip(bounds, bounds[1:])):
        la, lb = cumulative([a, b], mix)
        n = int(round(lb - la))
        if n <= 0:
            continue
        g = _rng(mix["base_seed"], 4, k, n).exponential(1.0, n + 1)
        g = g[_rng(seed, 4, k).permutation(n + 1)]
        due = inverse(la + (lb - la) * np.cumsum(g)[:n] / g.sum(), mix)
        for p, t in zip(sessions(mix, seed, n, k), due):
            p.sid, p.due = len(out), float(t)
            out.append(p)
    return out


def prefix_tokens(mix: dict, seed: int, vocab: int) -> list[np.ndarray]:
    return [_rng(seed, 5, i).integers(2, vocab, n, dtype=np.int32)
            for i, n in enumerate(prefix_lengths(mix))]


def suffix_tokens(seed: int, sid: int, n: int, vocab: int) -> np.ndarray:
    return _rng(seed, 6, sid).integers(2, vocab, n, dtype=np.int32)


def draft_rng(seed: int, sid: int) -> np.random.Generator:
    return _rng(seed, 7, sid)
