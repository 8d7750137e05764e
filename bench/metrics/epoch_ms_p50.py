"""Median host span of a server step that ran a verify batch, in the
window."""
from bench.metrics._common import median, verify_steps


def read(w):
    return median([(s.t1 - s.t0) * 1e3 for s in verify_steps(w)])
