"""Pure-jnp oracle for paged decode attention.

Gathers pages through the block table into dense KV and runs masked
attention of the single new token per sequence.
"""
from __future__ import annotations

import jax.numpy as jnp


def gather_pages(pages, block_table):
    """pages: (n_pages, Hkv, P, D); block_table: (B, n_max) ->
    dense token-major (B, n_max*P, Hkv, D)."""
    g = pages[block_table]                       # (B, n_max, Hkv, P, D)
    B, n_max, Hkv, P, D = g.shape
    return g.transpose(0, 1, 3, 2, 4).reshape(B, n_max * P, Hkv, D)


def paged_verify_attention_ref(
    q,                 # (B, T, H, D) new tokens at positions base..base+T-1
    k_pages,           # (n_pages, Hkv, P, D) — new K/V already scattered in
    v_pages,           # (n_pages, Hkv, P, D)
    block_table,       # (B, n_max) int32
    base_lens,         # (B,) int32 committed kv tokens BEFORE the new block
    *,
    softcap: float = 0.0,
    scale=None,
):
    """Oracle for speculative verification over paged KV: query t of row b
    attends to kv positions < base_lens[b] + t + 1 (history + the new
    tokens up to and including itself)."""
    B, T, H, D = q.shape
    Hkv = k_pages.shape[1]
    G = H // Hkv
    scale = scale if scale is not None else D**-0.5
    k = gather_pages(k_pages, block_table).astype(jnp.float32)
    v = gather_pages(v_pages, block_table).astype(jnp.float32)
    S = k.shape[1]
    qg = q.reshape(B, T, Hkv, G, D).astype(jnp.float32)
    s = jnp.einsum("bthgd,bshd->bhgts", qg, k) * scale
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    lens = base_lens[:, None] + jnp.arange(T)[None, :] + 1          # (B, T)
    mask = jnp.arange(S)[None, None, :] < lens[:, :, None]          # (B, T, S)
    s = jnp.where(mask[:, None, None], s, -1e30)
    p = jnp.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    o = jnp.einsum("bhgts,bshd->bthgd", p, v)
    return o.reshape(B, T, H, D).astype(q.dtype)


def paged_attention_ref(
    q,                 # (B, H, D) one new token per sequence
    k_pages,           # (n_pages, Hkv, P, D)
    v_pages,           # (n_pages, Hkv, P, D)
    block_table,       # (B, n_max) int32
    lengths,           # (B,) int32 valid kv tokens (including current)
    *,
    softcap: float = 0.0,
    scale=None,
):
    B, H, D = q.shape
    Hkv = k_pages.shape[1]
    G = H // Hkv
    scale = scale if scale is not None else D**-0.5
    k = gather_pages(k_pages, block_table).astype(jnp.float32)
    v = gather_pages(v_pages, block_table).astype(jnp.float32)
    S = k.shape[1]
    qg = q.reshape(B, Hkv, G, D).astype(jnp.float32)
    s = jnp.einsum("bhgd,bshd->bhgs", qg, k) * scale
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    mask = jnp.arange(S)[None, :] < lengths[:, None]      # (B, S)
    s = jnp.where(mask[:, None, None], s, -1e30)
    p = jnp.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    o = jnp.einsum("bhgs,bshd->bhgd", p, v)
    return o.reshape(B, H, D).astype(q.dtype)
