"""Accepted draft tokens over drafted tokens, from the verdicts of blocks
due in the window: one minus the paper's wasted-drafting ratio."""
import math

from bench.metrics._common import rounds


def read(w):
    acc = dra = 0
    for _, r in rounds(w):
        if w.in_window(r.due) and not math.isnan(r.t_done):
            acc += r.accept_len
            dra += len(r.draft)
    return 100.0 * acc / dra if dra else None
