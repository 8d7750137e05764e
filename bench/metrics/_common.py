"""Helpers shared by the metric readers (each reader is one file of this
directory, found by the metric's name; ``read(run)`` returns a number, or
``None`` where the run has nothing for it to read)."""
from __future__ import annotations

import math

from bench.stats import percentile


def in_window_latencies(w, pairs):
    """``pairs``: (due, done) -> (latencies in ms of the answered ones due
    in the window, misses)."""
    vals, miss = [], 0
    for due, done in pairs:
        if not w.in_window(due):
            continue
        if math.isnan(done):
            miss += 1
        else:
            vals.append((done - due) * 1e3)
    return vals, miss


def p95_with_misses(w, pairs):
    """p95 of the latencies due in the window; a miss ranks above every
    answer and, where the rank lands on one, reads as its wait to the end
    of the run (a lower bound)."""
    vals, miss = in_window_latencies(w, pairs)
    v = percentile(vals, 95.0, miss)
    if v is not None or not miss:
        return v
    waits = [(w.t_end - due) * 1e3 for due, done in pairs
             if w.in_window(due) and math.isnan(done)]
    return max(waits + vals)


def rounds(w):
    for s in w.sessions.values():
        yield from ((s, r) for r in s.rounds)


def verify_steps(w):
    return [st for st in w.steps if st.verify_rows and w.in_window(st.t0)]


def traced_steps(w):
    a, b = w.trace_span
    return [st for st in w.steps if st.t0 >= a and st.t1 <= b]


def median(xs):
    xs = sorted(xs)
    if not xs:
        return None
    n = len(xs)
    return xs[n // 2] if n % 2 else (xs[n // 2 - 1] + xs[n // 2]) / 2
