"""Pallas TPU kernel: paged decode attention (PagedAttention, TPU-native).

vLLM pages (16 tokens, per-SM gather) do not map to TPU; instead a page IS
a KV tile (256 tokens = one DMA) and the block table drives the BlockSpec
``index_map`` through scalar prefetch — page lookup becomes tile prefetch,
the TPU-idiomatic equivalent of paged gathering (DESIGN.md §2).

Pool layout (DESIGN.md §2): ``(n_pages, Hkv, P, D)`` — the KV head sits
ahead of the in-page token axis, so one (page, head) tile is a ``(1, 1, P,
D)`` block whose last two dims are the full ``(P, D)`` of the array: the
shape the TPU lowering requires (a head sliced out of the second-minor axis
of a token-major page is refused).

grid = (B, Hkv, n_max_pages); the online-softmax state for the single query
token (x G group heads) lives in VMEM scratch across the page loop.

Both dots run at the pages' precision: f32 pages take ``HIGHEST`` (the
MXU's default is one bf16 pass, which would round q.k and p.v to bf16 on
the chip while the CPU computes them in f32); bf16 pages keep the default.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(
    block_table_ref,     # scalar prefetch: (B, n_max) int32
    lengths_ref,         # scalar prefetch: (B,) int32
    q_ref,               # (1, 1, G, D)
    k_ref,               # (1, 1, P, D)   (page, head) selected by index_map
    v_ref,               # (1, 1, P, D)
    o_ref,               # (1, 1, G, D)
    m_scr,               # (G, 1)
    l_scr,               # (G, 1)
    acc_scr,             # (G, D)
    *,
    page: int,
    n_max: int,
    softcap: float,
    scale: float,
    precision,
):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = lengths_ref[b]

    @pl.when(j * page < length)
    def _compute():
        q = q_ref[0, 0, 0].astype(jnp.float32)              # (G, D)
        k = k_ref[0, 0].astype(jnp.float32)                 # (P, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), precision=precision,
            preferred_element_type=jnp.float32,
        ) * scale                                           # (G, P)
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        kv_pos = j * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kv_pos < length, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), precision=precision,
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = m_new

    @pl.when(j == n_max - 1)
    def _finish():
        o_ref[0, 0, 0] = (
            acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        ).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("softcap", "interpret")
)
def paged_attention(
    q,                 # (B, H, D)
    k_pages,           # (n_pages, Hkv, P, D)
    v_pages,           # (n_pages, Hkv, P, D)
    block_table,       # (B, n_max) int32
    lengths,           # (B,) int32
    *,
    softcap: float = 0.0,
    interpret: bool = False,
):
    B, H, D = q.shape
    n_pages, Hkv, P, _ = k_pages.shape
    G = H // Hkv
    n_max = block_table.shape[1]
    qg = q.reshape(B, 1, Hkv, G, D)

    precision = (jax.lax.Precision.HIGHEST
                 if k_pages.dtype == jnp.float32 else None)
    kernel = functools.partial(
        _kernel, page=P, n_max=n_max, softcap=softcap, scale=D**-0.5,
        precision=precision,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv, n_max),
        in_specs=[
            pl.BlockSpec((1, 1, 1, G, D), lambda b, h, j, bt, L: (b, 0, h, 0, 0)),
            # the paged lookup: page id comes from the scalar-prefetched table
            pl.BlockSpec((1, 1, P, D), lambda b, h, j, bt, L: (bt[b, j], h, 0, 0)),
            pl.BlockSpec((1, 1, P, D), lambda b, h, j, bt, L: (bt[b, j], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, 1, G, D), lambda b, h, j, bt, L: (b, 0, h, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, 1, Hkv, G, D), q.dtype),
        interpret=interpret,
    )(block_table.astype(jnp.int32), lengths.astype(jnp.int32), qg, k_pages, v_pages)
    return out.reshape(B, H, D)
