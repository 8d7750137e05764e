"""Device time of the paged prefill program (module ``jit_prefill_paged``)
in the traced window, in ms per 1000 prompt tokens that the steps inside
the window prefilled.  A program without that module name reads nothing."""
from bench import trace as T
from bench.metrics._common import traced_steps

#: substrings of the prefill program's module name in the trace
MODULE_NAMES = ("jit_prefill_paged",)


def read(w):
    if w.trace is None:
        return None
    t = sum(d for n, _, d in T.clip(w.trace.modules, w.trace.t0, w.trace.t1)
            if any(k in n for k in MODULE_NAMES))
    tokens = sum(n for st in traced_steps(w) for _, n in st.prefill_rows)
    if t <= 0 or not tokens:
        return None
    return 1e3 * t / (tokens / 1e3)
