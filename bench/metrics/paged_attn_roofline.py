"""The paged-attention kernel's share of its roofline: the least time its
needed work could take at the chip's peaks (each row's K/V history read
once, plus its queries and outputs; causal QK^T and PV FLOPs), over the
kernel's device time in the traced window.  Every verify and prefill
step inside the traced window calls it once per layer."""
from bench import trace as T
from bench import work
from bench.common import log
from bench.metrics._common import traced_steps

#: substrings of the kernel's operation name in the trace
KERNEL_NAMES = ("paged_attention",)


def kernel_time(red) -> float:
    return sum(d for n, _, d in T.clip(red.ops, red.t0, red.t1)
               if any(k in n for k in KERNEL_NAMES))


def read(w):
    if w.trace is None:
        return None
    t = kernel_time(w.trace)
    rows = [r for st in traced_steps(w) for r in st.verify_rows + st.prefill_rows]
    if t <= 0 or not rows:
        return None
    flops = sum(work.attention_flops(w.config, b, n) for b, n in rows)
    nbytes = sum(work.attention_bytes(w.config, b, n) for b, n in rows)
    least, bound = work.roofline_s(flops, nbytes, w.peaks)
    log("paged_attn_roofline", bound=bound, kernel_s=t, flops=flops,
        bytes=nbytes, rows=len(rows))
    return 100.0 * least / t
