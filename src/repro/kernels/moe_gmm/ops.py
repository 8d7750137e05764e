"""Public entry for the MoE layer's grouped expert matmul: Pallas on the
TPU, interpret mode on the CPU (`repro.kernels.backend`).

``moe_gmm_op`` runs every routed expert's SwiGLU over the rows routed to
it, as two grouped matmuls (gate and up in one pass, then down) under the
jitted wrapper ``moe_gmm``, whose name the kernels' ops carry in a device
trace.  The caller sorts the rows by expert and pads them to
``padded_rows``; ``row_tile`` sets the row tile for that many rows.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import backend
from repro.kernels.moe_gmm.moe_gmm import moe_gmm
from repro.kernels.moe_gmm.ref import moe_gmm_ref


def row_tile(m: int) -> int:
    """Rows per tile for ``m`` sorted rows.  Each visit of a row tile reads
    its expert's weights once, and a group that straddles two tiles is
    visited twice, so a larger tile reads fewer weights twice but computes
    more masked rows.  A verify epoch (up to 16 x 9 tokens x top-6, about
    14 rows an expert) takes 128; a prefill epoch (16 x 64 x 6, about 96 an
    expert) 256; a smaller call one tile of its own size."""
    if m > 2048:
        return 256
    return min(128, -(-m // 16) * 16)


def padded_rows(m: int) -> int:
    """``m`` rounded up to whole row tiles."""
    t = row_tile(m)
    return -(-m // t) * t


def moe_gmm_op(x, w_gate, w_up, w_down, group_sizes, layer=None):
    """``x`` (M, D) rows sorted by expert, M = ``padded_rows`` of the real
    rows; ``group_sizes`` (E,) int32.  The weights are one layer's, (E, D,
    F) / (E, F, D), or stacked over layers with ``layer`` the index of the
    one to use.  Returns (M, D) float32; rows past the last group are
    unspecified."""
    if layer is None:
        w_gate, w_up, w_down = w_gate[None], w_up[None], w_down[None]
        layer = 0
    return moe_gmm(x, w_gate, w_up, w_down, group_sizes,
                   jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)),
                   tm=row_tile(x.shape[0]), interpret=backend.interpret())


__all__ = ["moe_gmm_op", "moe_gmm_ref", "padded_rows", "row_tile"]
