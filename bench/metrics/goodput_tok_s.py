"""The paper's goodput: tokens committed in the window by sessions that
met both of their class's SLOs -- the first token within the class's TTFT
limit, and a committed rate (tokens after the first, over the time from
the first token to the last verdict) at or above the class's tokens/s.
The SLO numbers are the mix file's copies of the paper's classes."""
import math

from bench.metrics.committed_tok_s import committed_in_window


def met_slo(w, s) -> bool:
    slo = w.mix["slo"]
    c = str(s.plan.edge["slo_class"])
    if math.isnan(s.t_first) or s.t_first - s.plan.due > slo["ttft_s"][c]:
        return False
    if not s.commits:
        return True
    span = s.commits[-1][0] - s.t_first
    n = sum(k for _, k in s.commits)
    return span <= 0 or n / span >= slo["tok_s"][c]


def read(w):
    return sum(committed_in_window(w, s) for s in w.sessions.values()
               if met_slo(w, s)) / w.seconds
