"""Decoder-only transformer LM covering the dense / moe / vlm families.

Layer stacks are ``lax.scan``-ed over stacked parameters so HLO size is
O(1) in depth (required for 100-layer dry-runs).  The VLM variant scans over
*groups* of (cross_attn_every self layers + 1 gated cross-attention layer).

Three entry points (shared across families, see `repro.models.zoo`):
  * forward_train(params, batch)              -> logits (B, S, V)
  * prefill(params, batch, cache)             -> (logits, cache)
  * decode(params, tokens, cache, pos)        -> (logits (B,T,V), cache)
    (T = 1 for decode, K+1 for speculative verification)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.common import loops

from repro.common.sharding import NULL_CTX
from repro.configs.base import ArchConfig
from repro.kernels.paged_attention.ops import flat_pools, write_and_attend
from repro.models import layers as L
from repro.models.moe import (
    EXPERT_WEIGHTS,
    init_moe,
    moe_apply,
    moe_axes,
    moe_serve,
)


def attn_spec(cfg: ArchConfig) -> L.AttnSpec:
    return L.AttnSpec(
        d_model=cfg.d_model,
        n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.resolved_head_dim,
        rope_theta=cfg.rope_theta,
        qkv_bias=cfg.qkv_bias,
        softcap=cfg.attn_softcap,
        window=cfg.sliding_window,
    )


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _init_block(cfg: ArchConfig, rng, dtype):
    spec = attn_spec(cfg)
    ka, km = jax.random.split(rng)
    p = {
        "ln1": L.init_rmsnorm(cfg.d_model, dtype),
        "attn": L.init_attention(ka, spec, dtype),
        "ln2": L.init_rmsnorm(cfg.d_model, dtype),
    }
    if cfg.moe is not None:
        p["moe"] = init_moe(km, cfg.d_model, cfg.d_ff, cfg.moe, dtype)
    else:
        p["mlp"] = L.init_mlp(km, cfg.d_model, cfg.d_ff, dtype, cfg.gated_mlp)
    if cfg.sandwich_norm:
        p["ln1_post"] = L.init_rmsnorm(cfg.d_model, dtype)
        p["ln2_post"] = L.init_rmsnorm(cfg.d_model, dtype)
    return p


def _block_axes(cfg: ArchConfig):
    spec = attn_spec(cfg)
    a = {
        "ln1": ("embed",),
        "attn": L.attention_axes(spec),
        "ln2": ("embed",),
    }
    if cfg.moe is not None:
        a["moe"] = moe_axes(cfg.moe)
    else:
        a["mlp"] = L.mlp_axes(cfg.gated_mlp)
    if cfg.sandwich_norm:
        a["ln1_post"] = ("embed",)
        a["ln2_post"] = ("embed",)
    return a


def _init_cross_block(cfg: ArchConfig, rng, dtype):
    spec = attn_spec(cfg)
    ka, km = jax.random.split(rng)
    return {
        "ln1": L.init_rmsnorm(cfg.d_model, dtype),
        "attn": L.init_attention(ka, spec, dtype),
        "ln2": L.init_rmsnorm(cfg.d_model, dtype),
        "mlp": L.init_mlp(km, cfg.d_model, cfg.d_ff, dtype, cfg.gated_mlp),
        "gate_attn": jnp.zeros((), jnp.float32),
        "gate_mlp": jnp.zeros((), jnp.float32),
    }


def _cross_block_axes(cfg: ArchConfig):
    spec = attn_spec(cfg)
    return {
        "ln1": ("embed",),
        "attn": L.attention_axes(spec),
        "ln2": ("embed",),
        "mlp": L.mlp_axes(cfg.gated_mlp),
        "gate_attn": (),
        "gate_mlp": (),
    }


def _stack_init(init_fn, rng, n):
    return jax.vmap(init_fn)(jax.random.split(rng, n))


def _stack_axes(axes, extra=("layers",)):
    return jax.tree.map(
        lambda a: (*extra, *a), axes, is_leaf=lambda x: isinstance(x, tuple)
    )


def init_params(cfg: ArchConfig, rng, dtype=jnp.bfloat16):
    ke, kl, kc, ku = jax.random.split(rng, 4)
    p = {
        "embed": L.init_embedding(ke, cfg.vocab, cfg.d_model, dtype),
        "final_norm": L.init_rmsnorm(cfg.d_model, dtype),
    }
    if cfg.cross_attn_every:
        n_groups = cfg.n_layers // cfg.cross_attn_every
        per = cfg.cross_attn_every

        def group_init(k):
            k1, k2 = jax.random.split(k)
            return {
                "self": _stack_init(lambda kk: _init_block(cfg, kk, dtype), k1, per),
                "cross": _init_cross_block(cfg, k2, dtype),
            }

        p["groups"] = _stack_init(group_init, kl, n_groups)
    else:
        p["blocks"] = _stack_init(
            lambda kk: _init_block(cfg, kk, dtype), kl, cfg.n_layers
        )
    if not cfg.tie_embeddings:
        p["unembed"] = L.dense_param(ku, cfg.d_model, (cfg.vocab,), dtype)
    return p


def param_axes(cfg: ArchConfig):
    a = {
        "embed": ("vocab", "embed"),
        "final_norm": ("embed",),
    }
    if cfg.cross_attn_every:
        a["groups"] = {
            "self": _stack_axes(_block_axes(cfg), ("layers", "layers_inner")),
            "cross": _stack_axes(_cross_block_axes(cfg)),
        }
    else:
        a["blocks"] = _stack_axes(_block_axes(cfg))
    if not cfg.tie_embeddings:
        a["unembed"] = ("embed", "vocab")
    return a


# ---------------------------------------------------------------------------
# block application
# ---------------------------------------------------------------------------


def _apply_block_full(cfg, spec, bp, x, *, local, ctx, kv_chunk=1024):
    """Self-attn + FFN over the full sequence (train/prefill). Returns
    (x, (k, v), aux)."""
    h = L.rmsnorm(x, bp["ln1"], cfg.norm_eps)
    att, (k, v) = L.self_attention(
        bp["attn"], h, spec, local=local, kv_chunk=kv_chunk, ctx=ctx
    )
    if cfg.sandwich_norm:
        att = L.rmsnorm(att, bp["ln1_post"], cfg.norm_eps)
    x = x + att
    x = ctx.cs(x, ("act_batch", "act_seq", "act_embed"))
    h = L.rmsnorm(x, bp["ln2"], cfg.norm_eps)
    aux = {}
    if cfg.moe is not None:
        f, aux = moe_apply(bp["moe"], h, cfg.moe, ctx=ctx)
    else:
        f = L.mlp_apply(bp["mlp"], h, cfg.gated_mlp, ctx=ctx)
    if cfg.sandwich_norm:
        f = L.rmsnorm(f, bp["ln2_post"], cfg.norm_eps)
    x = x + f
    x = ctx.cs(x, ("act_batch", "act_seq", "act_embed"))
    return x, (k, v), aux


def _apply_block_cached(cfg, spec, bp, x, kc, vc, pos, *, local, ctx):
    """Self-attn + FFN for new tokens against a KV cache (decode/verify)."""
    h = L.rmsnorm(x, bp["ln1"], cfg.norm_eps)
    att, (kc, vc) = L.cached_attention(
        bp["attn"], h, spec, kc, vc, pos, local=local, ctx=ctx
    )
    if cfg.sandwich_norm:
        att = L.rmsnorm(att, bp["ln1_post"], cfg.norm_eps)
    x = x + att
    h = L.rmsnorm(x, bp["ln2"], cfg.norm_eps)
    if cfg.moe is not None:
        # dropless: verification must not depend on microbatch composition
        f = moe_serve(bp["moe"], h, cfg.moe)
    else:
        f = L.mlp_apply(bp["mlp"], h, cfg.gated_mlp, ctx=ctx)
    if cfg.sandwich_norm:
        f = L.rmsnorm(f, bp["ln2_post"], cfg.norm_eps)
    return x + f, kc, vc


def _apply_cross_block(cfg, spec, cp, x, k_img, v_img, *, ctx):
    h = L.rmsnorm(x, cp["ln1"], cfg.norm_eps)
    att = L.cross_attention(cp["attn"], h, spec, k_img, v_img)
    # gates are f32 scalars; cast so the residual keeps the activation dtype
    x = x + jnp.tanh(cp["gate_attn"]).astype(x.dtype) * att.astype(x.dtype)
    h = L.rmsnorm(x, cp["ln2"], cfg.norm_eps)
    f = L.mlp_apply(cp["mlp"], h, cfg.gated_mlp, ctx=ctx)
    return x + jnp.tanh(cp["gate_mlp"]).astype(x.dtype) * f.astype(x.dtype)


def _is_local_flags(cfg: ArchConfig, n):
    if cfg.local_global_alternate:
        return (jnp.arange(n) % 2 == 0)
    return jnp.zeros((n,), bool)


def _embed_in(cfg, params, tokens):
    x = L.embed(params["embed"], tokens)
    if cfg.scale_embed:
        x = x * jnp.asarray(cfg.d_model**0.5, x.dtype)
    return x


def _logits(cfg, params, x):
    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    if cfg.tie_embeddings:
        return L.logits_out(x, params["embed"], tied=True, softcap=cfg.final_softcap)
    return L.logits_out(x, params["unembed"], tied=False, softcap=cfg.final_softcap)


def chunked_ce_loss(cfg, params, x, targets, *, ctx=NULL_CTX, chunk=512):
    """Fused final-norm + unembed + cross-entropy, scanned over sequence
    chunks so the (B, S, V) logits tensor is never materialized (vocab-heavy
    archs would need TBs otherwise).  Returns (loss_sum, n_tokens)."""
    B, S, D = x.shape
    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    table = params["embed"] if cfg.tie_embeddings else params["unembed"]
    Sc = min(chunk, S)
    pad = (-S) % Sc
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)), constant_values=-1)
    nc = (S + pad) // Sc
    xs = jnp.moveaxis(x.reshape(B, nc, Sc, D), 1, 0)
    ts = jnp.moveaxis(targets.reshape(B, nc, Sc), 1, 0)

    def body(acc, inp):
        xc, tc = inp
        lg = L.logits_out(
            xc, table, tied=cfg.tie_embeddings, softcap=cfg.final_softcap
        )                                                  # (B, Sc, V) f32
        lg = ctx.cs(lg, ("act_batch", None, "vocab"))
        lse = jax.nn.logsumexp(lg, axis=-1)
        tgt = jnp.take_along_axis(
            lg, jnp.maximum(tc, 0)[..., None], axis=-1
        )[..., 0]
        valid = (tc >= 0).astype(jnp.float32)
        acc = acc + jnp.sum((lse - tgt) * valid)
        return acc, None

    loss_sum, _ = loops.scan(body, jnp.zeros((), jnp.float32), (xs, ts))
    n_tok = jnp.maximum((targets >= 0).sum(), 1)
    return loss_sum, n_tok


# ---------------------------------------------------------------------------
# full-sequence forward (train / prefill)
# ---------------------------------------------------------------------------


def _run_layers_full(cfg, params, x, *, ctx, collect_kv, image_embeds=None,
                     remat=False):
    """Returns (x, kv_stack or None, cross_kv or None, aux)."""
    spec = attn_spec(cfg)
    ckpt = (
        (lambda f: jax.checkpoint(f, policy=jax.checkpoint_policies.nothing_saveable))
        if remat
        else (lambda f: f)
    )

    if cfg.cross_attn_every:
        per = cfg.cross_attn_every

        @ckpt
        def group_body(carry, gp):
            x, aux_acc = carry
            flags = jnp.zeros((per,), bool)

            def self_body(xc, inp):
                bp, loc = inp
                xo, (k, v), aux = _apply_block_full(
                    cfg, spec, bp, xc, local=loc, ctx=ctx
                )
                return xo, (k, v)

            x, kvs = loops.scan(self_body, x, (gp["self"], flags))
            kimg, vimg = L.cross_kv(gp["cross"]["attn"], image_embeds, spec)
            x = _apply_cross_block(cfg, spec, gp["cross"], x, kimg, vimg, ctx=ctx)
            return (x, aux_acc), (kvs, (kimg, vimg))

        (x, _), (kv_stack, cross_kv) = loops.scan(
            group_body, (x, 0.0), params["groups"]
        )
        return x, kv_stack, cross_kv, {}

    flags = _is_local_flags(cfg, cfg.n_layers)

    @ckpt
    def body(carry, inp):
        x, lb = carry
        bp, loc = inp
        x, (k, v), aux = _apply_block_full(
            cfg, spec, bp, x, local=loc, ctx=ctx
        )
        lb = lb + aux.get("load_balance", 0.0)
        return (x, lb), ((k, v) if collect_kv else None)

    (x, lb), kv_stack = loops.scan(body, (x, 0.0), (params["blocks"], flags))
    return x, kv_stack, None, {"load_balance": lb / cfg.n_layers}


def forward_train(cfg: ArchConfig, params, batch, *, ctx=NULL_CTX, remat=False):
    """batch: {'tokens': (B,S) [, 'image_embeds', 'targets']}.

    Returns (logits, aux) — or (mean_ce_loss, aux) when 'targets' is present
    (fused chunked loss: full logits never materialized)."""
    tokens = batch["tokens"]
    x = _embed_in(cfg, params, tokens)
    x = ctx.cs(x, ("act_batch", "act_seq", "act_embed"))
    x, _, _, aux = _run_layers_full(
        cfg, params, x, ctx=ctx, collect_kv=False,
        image_embeds=batch.get("image_embeds"), remat=remat,
    )
    if "targets" in batch:
        loss_sum, n = chunked_ce_loss(cfg, params, x, batch["targets"], ctx=ctx)
        return loss_sum / n.astype(jnp.float32), aux
    return _logits(cfg, params, x), aux


# ---------------------------------------------------------------------------
# cache init / prefill / decode
# ---------------------------------------------------------------------------


def init_cache(cfg: ArchConfig, B, max_len, dtype=jnp.bfloat16):
    hkv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    kv = lambda: jnp.zeros((cfg.n_layers, B, max_len, hkv, hd), dtype)
    c = {"k": kv(), "v": kv()}
    if cfg.cross_attn_every:
        n_groups = cfg.n_layers // cfg.cross_attn_every
        c["k"] = c["k"].reshape(
            n_groups, cfg.cross_attn_every, B, max_len, hkv, hd
        )
        c["v"] = c["v"].reshape(
            n_groups, cfg.cross_attn_every, B, max_len, hkv, hd
        )
        c["k_img"] = jnp.zeros((n_groups, B, cfg.num_image_tokens, hkv, hd), dtype)
        c["v_img"] = jnp.zeros((n_groups, B, cfg.num_image_tokens, hkv, hd), dtype)
    return c


def cache_axes(cfg: ArchConfig):
    if cfg.cross_attn_every:
        kv = ("layers", "layers_inner", "act_batch", "act_cache", "act_kv", None)
        return {
            "k": kv,
            "v": kv,
            "k_img": ("layers", "act_batch", None, "act_kv", None),
            "v_img": ("layers", "act_batch", None, "act_kv", None),
        }
    kv = ("layers", "act_batch", "act_cache", "act_kv", None)
    return {"k": kv, "v": kv}


def prefill(cfg: ArchConfig, params, batch, cache, *, ctx=NULL_CTX,
            last_only: bool = False):
    """Run the prompt through the model, filling cache[: S]. Returns
    (logits, cache); ``last_only`` keeps only the final position's logits
    (serving prefill — avoids materializing the (B, S, V) tensor)."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = _embed_in(cfg, params, tokens)
    x = ctx.cs(x, ("act_batch", "act_seq", "act_embed"))
    # The dense slot backend's MoE prefill keeps GShard capacity, as
    # training does: routing groups are batch rows, so the ranking depends
    # only on the request's own tokens.  Its decode and the paged programs
    # route every token through the dropless grouped experts
    # (`moe.moe_serve`).
    x, kv_stack, cross_kv, _ = _run_layers_full(
        cfg, params, x, ctx=ctx, collect_kv=True,
        image_embeds=batch.get("image_embeds"),
    )
    k_new, v_new = kv_stack
    upd = lambda c, n: jax.lax.dynamic_update_slice_in_dim(
        c, n.astype(c.dtype), 0, axis=c.ndim - 3
    )
    cache = dict(cache)
    cache["k"] = upd(cache["k"], k_new)
    cache["v"] = upd(cache["v"], v_new)
    if cross_kv is not None:
        cache["k_img"] = cross_kv[0].astype(cache["k_img"].dtype)
        cache["v_img"] = cross_kv[1].astype(cache["v_img"].dtype)
    if last_only:
        x = x[:, -1:]
    return _logits(cfg, params, x), cache


def _apply_block_paged(
    cfg, spec, bp, x, kf, vf, layer, n_pages, block_table, base_lens, t_lens,
    *, ctx, experts=None,
):
    """Self-attn + FFN for new tokens against layer ``layer``'s KV pages in
    the flat pools (paged decode/verify; DESIGN.md §2).  New K/V are
    scattered through the block table before attention so causality within
    the new block falls out of the per-row length pointers.  ``experts``:
    the MoE experts' weights stacked over layers (`moe.moe_serve`)."""
    B, T, _ = x.shape
    h = L.rmsnorm(x, bp["ln1"], cfg.norm_eps)
    positions = base_lens[:, None] + jnp.arange(T)[None, :]
    q, k, v = L.qkv_proj(bp["attn"], h, spec, positions)
    o, kf, vf = write_and_attend(
        q, k, v, kf, vf, layer, n_pages, block_table, base_lens, t_lens,
        softcap=spec.softcap,
    )
    att = L.attn_out(bp["attn"], o)
    if cfg.sandwich_norm:
        att = L.rmsnorm(att, bp["ln1_post"], cfg.norm_eps)
    x = x + att
    h = L.rmsnorm(x, bp["ln2"], cfg.norm_eps)
    if cfg.moe is not None:
        # verify and prefill alike: dropless grouped experts, pad
        # positions left out (a token's result cannot depend on the batch)
        valid = jnp.arange(T)[None, :] < t_lens[:, None]
        f = moe_serve(bp["moe"], h, cfg.moe, valid, experts=experts,
                      layer=layer)
    else:
        f = L.mlp_apply(bp["mlp"], h, cfg.gated_mlp, ctx=ctx)
    if cfg.sandwich_norm:
        f = L.rmsnorm(f, bp["ln2_post"], cfg.norm_eps)
    return x + f, kf, vf


def decode_paged(
    cfg: ArchConfig,
    params,
    tokens,            # (B, T) new tokens at positions base_lens[b] + t
    k_pages,           # (n_layers, n_pages, Hkv, P, hd)
    v_pages,
    block_table,       # (B, n_max) int32 page ids per row
    base_lens,         # (B,) int32 committed kv tokens per row
    t_lens,            # (B,) int32 valid new tokens per row (<= T)
    cross_cache=None,  # vlm: {'k_img','v_img'} (n_groups, B, Ni, Hkv, hd)
    *,
    dropless: bool = True,
    ctx=NULL_CTX,
):
    """Paged-cache analogue of ``decode``: serves ragged prefill (T = prompt
    suffix) and speculative verification (T = K+1) against `PagedKV` storage.
    Returns (logits, (k_pages, v_pages)) with the new tokens' K/V scattered
    into the pages.  The layer scan carries the pools, as one flat pool
    each, and writes them in place (``write_and_attend``).  Requires full
    (non-windowed) attention — the paged kernel has no sliding-window mask
    (engine falls back to dense otherwise).  MoE layers route through the
    dropless grouped experts (`moe.moe_serve`) for verification and prompt
    prefill alike, so the result is exact under either ``dropless``; the
    keyword stays for callers that still pass it."""
    del dropless
    spec = attn_spec(cfg)
    if spec.window:
        raise ValueError("decode_paged does not support sliding-window attn")
    base_lens = jnp.asarray(base_lens, jnp.int32)
    t_lens = jnp.asarray(t_lens, jnp.int32)
    block_table = jnp.asarray(block_table, jnp.int32)
    x = _embed_in(cfg, params, tokens)
    x = ctx.cs(x, ("act_batch", None, "act_embed"))
    n_layers, n_pages = k_pages.shape[:2]
    kf, vf = flat_pools(k_pages, v_pages)
    layers = jnp.arange(n_layers, dtype=jnp.int32)
    blocks, experts = params.get("blocks"), None
    if cfg.moe is not None and not cfg.cross_attn_every:
        # the experts stay stacked outside the scan and the grouped matmul
        # reads layer ``l``'s from the stack: scanned, each layer's would be
        # cut out and copied for the kernel
        moe = dict(blocks["moe"])
        experts = tuple(moe.pop(k) for k in EXPERT_WEIGHTS)
        blocks = {**blocks, "moe": moe}

    def self_body(carry, inp):
        bp, layer = inp
        return _apply_block_paged(
            cfg, spec, bp, *carry, layer, n_pages, block_table, base_lens,
            t_lens, ctx=ctx, experts=experts,
        ), None

    if cfg.cross_attn_every:
        per = cfg.cross_attn_every

        def group_body(carry, inp):
            gp, group_layers, kimg, vimg = inp
            (x, kf, vf), _ = loops.scan(
                self_body, carry, (gp["self"], group_layers))
            x = _apply_cross_block(cfg, spec, gp["cross"], x, kimg, vimg, ctx=ctx)
            return (x, kf, vf), None

        body = group_body
        xs = (params["groups"], layers.reshape(n_layers // per, per),
              cross_cache["k_img"], cross_cache["v_img"])
    else:
        body, xs = self_body, (blocks, layers)

    with jax.named_scope("layer_scan"):
        (x, kf, vf), _ = loops.scan(body, (x, kf, vf), xs)
    return _logits(cfg, params, x), (kf.reshape(k_pages.shape),
                                     vf.reshape(v_pages.shape))


def vlm_cross_kv(cfg: ArchConfig, params, image_embeds):
    """Per-group gated-cross-attention K/V over the image embeddings —
    computed once at session open for the paged engine (the dense path
    computes these inside ``prefill``).  Returns (k, v) of shape
    (n_groups, B, Ni, Hkv, hd)."""
    spec = attn_spec(cfg)

    def body(c, gp):
        return c, L.cross_kv(gp["cross"]["attn"], image_embeds, spec)

    _, (k, v) = loops.scan(body, 0, params["groups"])
    return k, v


def decode(cfg: ArchConfig, params, tokens, cache, pos, *, ctx=NULL_CTX):
    """tokens: (B, T) new tokens at absolute positions pos..pos+T-1."""
    spec = attn_spec(cfg)
    x = _embed_in(cfg, params, tokens)
    x = ctx.cs(x, ("act_batch", None, "act_embed"))

    if cfg.cross_attn_every:
        def group_body(x, inp):
            gp, kc, vc, kimg, vimg = inp

            def self_body(xc, inner):
                bp, kci, vci = inner
                loc = jnp.asarray(False)
                xo, kci, vci = _apply_block_cached(
                    cfg, spec, bp, xc, kci, vci, pos, local=loc, ctx=ctx
                )
                return xo, (kci, vci)

            x, (kc, vc) = loops.scan(self_body, x, (gp["self"], kc, vc))
            x = _apply_cross_block(cfg, spec, gp["cross"], x, kimg, vimg, ctx=ctx)
            return x, (kc, vc)

        x, (k_new, v_new) = loops.scan(
            group_body,
            x,
            (params["groups"], cache["k"], cache["v"], cache["k_img"], cache["v_img"]),
        )
        cache = dict(cache, k=k_new, v=v_new)
        return _logits(cfg, params, x), cache

    flags = _is_local_flags(cfg, cfg.n_layers)

    def body(x, inp):
        bp, kc, vc, loc = inp
        x, kc, vc = _apply_block_cached(
            cfg, spec, bp, x, kc, vc, pos, local=loc, ctx=ctx
        )
        return x, (kc, vc)

    x, (k_new, v_new) = loops.scan(
        body, x, (params["blocks"], cache["k"], cache["v"], flags)
    )
    cache = dict(cache, k=k_new, v=v_new)
    return _logits(cfg, params, x), cache
