"""The fused verify program's share of the chip's peak: needed FLOPs of
the verify steps in the traced window (2 per active parameter per token,
MoE at top-k plus shared experts, the head per scored token, plus causal
attention) over the verify programs' device time x the bf16 peak."""
from bench import trace as T
from bench import work
from bench.metrics._common import traced_steps

#: substrings of the verify program's module name in the trace
MODULE_NAMES = ("jit_fused",)


def read(w):
    if w.trace is None:
        return None
    t = sum(d for n, _, d in T.clip(w.trace.modules, w.trace.t0, w.trace.t1)
            if any(k in n for k in MODULE_NAMES))
    rows = [r for st in traced_steps(w) for r in st.verify_rows]
    if t <= 0 or not rows:
        return None
    return 100.0 * work.step_flops(w.config, rows) / (
        t * w.peaks["bf16_flops_per_s"])
