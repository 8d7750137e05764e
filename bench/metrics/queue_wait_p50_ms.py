"""Median wait of a draft block from its due upload to the start of the
server step that served it (benchmark host spans)."""
import math

from bench.metrics._common import median, rounds


def read(w):
    return median([(r.t_step0 - r.due) * 1e3 for _, r in rounds(w)
                   if w.in_window(r.due) and not math.isnan(r.t_step0)])
