"""Pure-jnp oracle for the grouped expert matmul: each expert's SwiGLU over
the rows of its group, one expert at a time, in float32 at ``highest``
precision; rows past the last group come back 0."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def moe_gmm_ref(x, w_gate, w_up, w_down, group_sizes):
    """``x`` (M, D) rows sorted by expert, ``group_sizes`` (E,) -> (M, D)."""
    E = w_gate.shape[0]
    ends = jnp.cumsum(group_sizes)
    group = jnp.searchsorted(ends, jnp.arange(x.shape[0]), side="right")
    x = x.astype(jnp.float32)

    def expert(y, e):
        with jax.default_matmul_precision("highest"):
            h = (jax.nn.silu(x @ w_gate[e].astype(jnp.float32))
                 * (x @ w_up[e].astype(jnp.float32)))
            out = h @ w_down[e].astype(jnp.float32)
        return jnp.where((group == e)[:, None], out, y), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(E))
    return y
