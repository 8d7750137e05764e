"""The grouped expert matmul's share of its roofline: the least time the
needed work of the routed experts could take at the chip's peaks
(`bench.moe_work`: per call of T real tokens, T*k assignments through
their experts' SwiGLU, the weights of min(E, T*k) experts read once), over
the ``moe_gmm`` kernels' device time in the traced window.  Every verify
and prefill call of a step inside the traced window runs them once per
layer.  A program without such kernels reads nothing."""
from bench import moe_work
from bench import trace as T
from bench import work
from bench.common import log
from bench.metrics._common import traced_steps

#: substrings of the kernels' operation names in the trace
KERNEL_NAMES = ("moe_gmm",)


def read(w):
    if w.trace is None or not w.config.get("n_routed_experts"):
        return None
    t = sum(d for n, _, d in T.clip(w.trace.ops, w.trace.t0, w.trace.t1)
            if any(k in n for k in KERNEL_NAMES))
    # one call of each program a step ran: all its verify rows, all its
    # prefill chunks
    calls = [sum(n for _, n in rows) for st in traced_steps(w)
             for rows in (st.verify_rows, st.prefill_rows) if rows]
    if t <= 0 or not calls:
        return None
    flops = sum(moe_work.gmm_flops(w.config, n) for n in calls)
    nbytes = sum(moe_work.gmm_bytes(w.config, n) for n in calls)
    least, bound = work.roofline_s(flops, nbytes, w.peaks)
    log("moe_gmm_roofline", bound=bound, kernel_s=t, flops=flops,
        bytes=nbytes, calls=len(calls))
    return 100.0 * least / t
