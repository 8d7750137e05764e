"""p95 time from a draft block's due upload to its verdict on the host,
over the blocks due in the window; an unanswered block is a miss."""
from bench.metrics._common import p95_with_misses, rounds


def read(w):
    return p95_with_misses(w, [(r.due, r.t_done) for _, r in rounds(w)])
