"""Needed work: the operations and bytes the algorithm needs, from shapes.

Counted from the configuration file, never from what a kernel or program
happens to do: no page read twice, no expert computed for a token it was
not routed to, no pad row.  So no correct implementation can take less
time than these over the chip's peaks, and a faster kernel or a leaner
MoE raises a share without the count going stale.
"""
from __future__ import annotations

from bench.reference import dims

BF16 = 2            # bytes per element of KV pages and activations


def active_params(c: dict) -> dict:
    """Parameters each token multiplies, by part: ``layer`` (per decoder
    layer: attention projections, the dense MLP or the router, the top-k
    routed experts and the shared experts) and ``head`` (the output
    projection).  The embedding lookup does no arithmetic."""
    d = dims(c)
    D, H, Hkv, hd = d["D"], d["H"], d["Hkv"], d["hd"]
    attn = D * H * hd + 2 * D * Hkv * hd + H * hd * D
    if c.get("n_routed_experts"):
        fe = c["moe_intermediate_size"]
        fs = fe * c.get("n_shared_experts", 0)
        ffn = (D * c["n_routed_experts"]
               + c["num_experts_per_tok"] * 3 * D * fe + 3 * D * fs)
    else:
        ffn = 3 * D * c["intermediate_size"]
    return {"layer": attn + ffn, "head": D * d["V"]}


def attention_flops(c: dict, base: int, n: int) -> float:
    """QK^T and PV for ``n`` new tokens after ``base`` cached ones, causal:
    query t attends to base + t + 1 positions; all layers."""
    d = dims(c)
    ctx = n * base + n * (n + 1) / 2
    return 4.0 * ctx * d["H"] * d["hd"] * d["L"]


def attention_bytes(c: dict, base: int, n: int) -> float:
    """The row's K and V history read once, plus its queries and outputs;
    all layers."""
    d = dims(c)
    kv = (base + n) * d["Hkv"] * d["hd"] * 2 * BF16
    qo = 2 * n * d["H"] * d["hd"] * BF16
    return float(kv + qo) * d["L"]


def step_flops(c: dict, rows: list) -> float:
    """A forward over ``rows`` of ``(base, n)``: matmuls at 2 FLOPs per
    active parameter per token, the head only where a token is scored,
    plus attention."""
    p = active_params(c)
    L = dims(c)["L"]
    tok = sum(n for _, n in rows)
    return (2.0 * tok * (p["layer"] * L + p["head"])
            + sum(attention_flops(c, b, n) for b, n in rows))


def roofline_s(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take, and which peak bounds it."""
    tf = flops / peaks["bf16_flops_per_s"]
    tb = nbytes / peaks["hbm_bytes_per_s"]
    return (tf, "flops") if tf >= tb else (tb, "bytes")
