"""p95 time to first token of the sessions due to open in the window, from
the due open time to the first token on the host; a session not answered
by the end of the run is a miss.  The window holds some 40 sessions, so
this tail is their third-longest wait: too noisy from run to run for an
end-to-end bound, it is read per layer beside `goodput_tok_s`, whose
sessions have to meet their first-token limit."""
from bench.metrics._common import p95_with_misses


def read(w):
    return p95_with_misses(w, [(s.plan.due, s.t_first)
                               for s in w.sessions.values()])
