"""Needed work of the routed-expert layer, from the configuration file.

For one call of the grouped expert matmul over ``tokens`` real tokens,
in every layer: each of the ``tokens * k`` routed assignments through
its expert's SwiGLU (gate, up and down: ``2 * 3 * D * fe`` FLOPs), the
weights of ``min(E, tokens * k)`` experts read once, and each
assignment's row read in and written out once in bfloat16.  No pad row,
no expert a token was not routed to, no weight read twice.

Which experts a call reaches is decided on the device; ``min(E,
tokens * k)`` is the most it can reach, so a small call whose tokens
share experts is counted as reading more weights than it needs.  The
calls of a step hold every verify row or prefill chunk of the step, so
nearly every call reaches all E.
"""
from __future__ import annotations

from bench.reference import dims
from bench.work import BF16


def _sizes(c: dict):
    return (dims(c)["D"], c["moe_intermediate_size"], c["n_routed_experts"],
            c["num_experts_per_tok"], dims(c)["L"])


def experts_read(c: dict, tokens: int) -> int:
    _, _, E, k, _ = _sizes(c)
    return min(E, tokens * k)


def gmm_flops(c: dict, tokens: int) -> float:
    D, fe, E, k, L = _sizes(c)
    return 2.0 * tokens * k * 3 * D * fe * L


def gmm_bytes(c: dict, tokens: int) -> float:
    D, fe, E, k, L = _sizes(c)
    weights = experts_read(c, tokens) * 3 * D * fe * BF16
    rows = 2 * tokens * k * D * BF16
    return float(weights + rows) * L
