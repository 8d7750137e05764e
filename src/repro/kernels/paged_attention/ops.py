"""Public entry for paged decode attention: Pallas on TPU, interpret mode
on the CPU (`repro.kernels.backend`).

Two consumers:
  * plain decode — ``paged_attention_op``, one query token per sequence;
  * speculative verification — ``paged_verify_attention_op``, K+1 query
    tokens per sequence.  The verify block is flattened to (B*T) single-
    token rows whose per-row length pointers encode causality (row (b, t)
    sees base_lens[b] + t + 1 kv tokens), so the same single-query kernel
    serves both paths and the block table stays the only addressing
    structure (DESIGN.md §2).

``scatter_kv_pages`` is the write path: new K/V land in the pages named
by the block table; masked/padded positions are redirected to the reserved
scratch page 0 so they can never clobber live pages.

``write_and_attend`` is one layer of a paged layer scan.  The scan carries
the stacked pools as one flat pool ``(n_layers * n_pages, Hkv, P, D)`` (a
bitcast of ``(n_layers, n_pages, ...)``) and layer ``l`` addresses its
pages as ``block_table + l * n_pages``, so the pools are written and read
in place: no layer's slice is cut out of the stack, copied or re-laid out
(DESIGN.md §2).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import backend
from repro.kernels.paged_attention.paged_attention import paged_attention as _kernel
from repro.kernels.paged_attention.ref import (
    gather_pages,
    paged_attention_ref,
    paged_verify_attention_ref,
)


def paged_attention_op(q, k_pages, v_pages, block_table, lengths, *, softcap=0.0):
    interpret = backend.interpret()
    return _kernel(
        q, k_pages, v_pages, block_table, lengths,
        softcap=softcap, interpret=interpret,
    )


def paged_verify_attention_op(
    q,                 # (B, T, H, D) new tokens at positions base..base+T-1
    k_pages,           # (n_pages, Hkv, P, D) — new K/V already scattered in
    v_pages,           # (n_pages, Hkv, P, D)
    block_table,       # (B, n_max) int32
    base_lens,         # (B,) int32 committed kv tokens BEFORE the new block
    *,
    softcap: float = 0.0,
):
    """Batched multi-token verification attention over paged KV.

    Requires the new tokens' K/V to be scattered into the pages first (see
    ``scatter_kv_pages``); causality within the block then falls out of the
    per-row length pointer alone."""
    B, T, H, D = q.shape
    n_max = block_table.shape[1]
    qf = q.reshape(B * T, H, D)
    btf = jnp.repeat(block_table, T, axis=0)                        # (B*T, n_max)
    lenf = (base_lens[:, None] + jnp.arange(T)[None, :] + 1).reshape(-1)
    out = paged_attention_op(
        qf, k_pages, v_pages, btf, lenf.astype(jnp.int32), softcap=softcap
    )
    return out.reshape(B, T, H, D)


def scatter_kv_pages(
    k_pages,           # (n_pages, Hkv, P, D) pages, of one layer or flat
    v_pages,
    k_new,             # (B, T, Hkv, D) K/V of the new tokens
    v_new,
    block_table,       # (B, n_max) int32 page ids within the layer
    base_lens,         # (B,) int32 write offset (committed kv tokens)
    t_lens,            # (B,) int32 valid new tokens per row (<= T)
    page_offset=0,     # int32 scalar: the layer's first page in a flat pool
):
    """Write new K/V through the block table.

    Row b token t lands at page page_offset + block_table[b, (base+t)//P],
    offset (base+t)%P.  Positions past t_lens[b] (draft-length padding,
    padded batch rows) are redirected to the layer's scratch page
    page_offset + 0: distinct live rows write disjoint pages, so the only
    scatter collisions are garbage-on-garbage inside the scratch page."""
    Hkv, P = k_pages.shape[1:3]
    B, T = k_new.shape[:2]
    n_max = block_table.shape[1]
    pos = base_lens[:, None] + jnp.arange(T)[None, :]               # (B, T)
    valid = jnp.arange(T)[None, :] < t_lens[:, None]                # (B, T)
    slot = jnp.clip(pos // P, 0, n_max - 1)
    pid = jnp.take_along_axis(block_table, slot, axis=1)            # (B, T)
    pid = jnp.where(valid, pid, 0) + page_offset
    off = jnp.where(valid, pos % P, 0)
    # an explicit head index, broadcast to (B, T, Hkv), addresses the
    # update as it comes, (B, T, Hkv, D): with a head slice between the
    # two advanced indices the compiler re-lays the pages out around the
    # scatter
    idx = (pid[..., None], jnp.arange(Hkv)[None, None, :], off[..., None])
    k_pages = k_pages.at[idx].set(k_new.astype(k_pages.dtype))
    v_pages = v_pages.at[idx].set(v_new.astype(v_pages.dtype))
    return k_pages, v_pages


def flat_pools(k_pages, v_pages):
    """``(n_layers, n_pages, ...)`` pools as one flat page pool each,
    ``(n_layers * n_pages, ...)``: a bitcast, not a copy."""
    flat = lambda p: p.reshape(p.shape[0] * p.shape[1], *p.shape[2:])
    return flat(k_pages), flat(v_pages)


def write_and_attend(
    q,                 # (B, T, H, D) queries of the new tokens
    k_new,             # (B, T, Hkv, D)
    v_new,
    k_flat,            # (n_layers * n_pages, Hkv, P, D) flat pools
    v_flat,
    layer,             # int32 scalar: the layer's index in the stack
    n_pages: int,      # pages per layer
    block_table,       # (B, n_max) int32 page ids within the layer
    base_lens,         # (B,) int32
    t_lens,            # (B,) int32
    *,
    softcap: float = 0.0,
):
    """One layer of a paged layer scan: scatter the new K/V into the
    layer's pages of the flat pools, then attend from them there.
    Returns ``(o, k_flat, v_flat)``."""
    page_offset = layer * n_pages
    with jax.named_scope("scatter_kv"):
        k_flat, v_flat = scatter_kv_pages(
            k_flat, v_flat, k_new, v_new, block_table, base_lens, t_lens,
            page_offset,
        )
    with jax.named_scope("paged_attention"):
        o = paged_verify_attention_op(
            q, k_flat, v_flat, block_table + page_offset, base_lens,
            softcap=softcap,
        )
    return o, k_flat, v_flat


__all__ = [
    "paged_attention_op",
    "paged_attention_ref",
    "paged_verify_attention_op",
    "paged_verify_attention_ref",
    "scatter_kv_pages",
    "flat_pools",
    "write_and_attend",
    "gather_pages",
]
