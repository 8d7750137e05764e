"""Whisper-style encoder-decoder backbone.

The audio conv frontend is a STUB per the assignment: ``input_specs()``
supplies precomputed frame embeddings (B, F, D).  Sinusoidal positions are
added on both sides (parameter-free, so decode positions are unbounded).
Decoder blocks: causal self-attention (cached) + cross-attention over the
encoder memory (KV computed once at prefill) + MLP.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.common import loops

from repro.common.sharding import NULL_CTX
from repro.configs.base import ArchConfig
from repro.kernels.paged_attention.ops import flat_pools, write_and_attend
from repro.models import layers as L
from repro.models.transformer import _stack_init, _stack_axes, attn_spec


def _sinusoid(positions, d):
    half = d // 2
    freq = jnp.exp(-jnp.arange(half, dtype=jnp.float32) * (9.210340371976184 / (half - 1)))
    ang = positions.astype(jnp.float32)[..., None] * freq
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def _spec(cfg):
    s = attn_spec(cfg)
    return L.AttnSpec(
        d_model=s.d_model,
        n_heads=s.n_heads,
        n_kv_heads=s.n_kv_heads,
        head_dim=s.head_dim,
        qkv_bias=s.qkv_bias,
        softcap=s.softcap,
        window=0,
        use_rope=False,          # whisper: absolute sinusoidal positions
    )


def _init_enc_block(cfg, rng, dtype):
    ka, km = jax.random.split(rng)
    return {
        "ln1": L.init_layernorm(cfg.d_model, dtype),
        "attn": L.init_attention(ka, _spec(cfg), dtype),
        "ln2": L.init_layernorm(cfg.d_model, dtype),
        "mlp": L.init_mlp(km, cfg.d_model, cfg.d_ff, dtype, cfg.gated_mlp),
    }


def _init_dec_block(cfg, rng, dtype):
    ka, kx, km = jax.random.split(rng, 3)
    return {
        "ln1": L.init_layernorm(cfg.d_model, dtype),
        "attn": L.init_attention(ka, _spec(cfg), dtype),
        "lnx": L.init_layernorm(cfg.d_model, dtype),
        "xattn": L.init_attention(kx, _spec(cfg), dtype),
        "ln2": L.init_layernorm(cfg.d_model, dtype),
        "mlp": L.init_mlp(km, cfg.d_model, cfg.d_ff, dtype, cfg.gated_mlp),
    }


def encdec_init(cfg: ArchConfig, rng, dtype=jnp.bfloat16):
    ke, k1, k2, kp = jax.random.split(rng, 4)
    return {
        "embed": L.init_embedding(ke, cfg.vocab, cfg.d_model, dtype),
        "frame_proj": L.dense_param(kp, cfg.d_model, (cfg.d_model,), dtype),
        "enc_blocks": _stack_init(
            lambda kk: _init_enc_block(cfg, kk, dtype), k1, cfg.encoder_layers
        ),
        "enc_norm": L.init_layernorm(cfg.d_model, dtype),
        "dec_blocks": _stack_init(
            lambda kk: _init_dec_block(cfg, kk, dtype), k2, cfg.n_layers
        ),
        "final_norm": L.init_rmsnorm(cfg.d_model, dtype),
    }


def encdec_axes(cfg: ArchConfig):
    ln = {"scale": ("embed",), "bias": ("embed",)}
    spec = _spec(cfg)
    enc = {
        "ln1": ln,
        "attn": L.attention_axes(spec),
        "ln2": ln,
        "mlp": L.mlp_axes(cfg.gated_mlp),
    }
    dec = {
        "ln1": ln,
        "attn": L.attention_axes(spec),
        "lnx": ln,
        "xattn": L.attention_axes(spec),
        "ln2": ln,
        "mlp": L.mlp_axes(cfg.gated_mlp),
    }
    return {
        "embed": ("vocab", "embed"),
        "frame_proj": ("embed", "embed2"),
        "enc_blocks": _stack_axes(enc),
        "enc_norm": ln,
        "dec_blocks": _stack_axes(dec),
        "final_norm": ("embed",),
    }


def encode(cfg, params, frames, *, ctx=NULL_CTX):
    """frames: (B, F, D) precomputed embeddings (stub frontend)."""
    spec = _spec(cfg)
    B, F, D = frames.shape
    x = jnp.einsum("bfd,de->bfe", frames, params["frame_proj"])
    x = x + _sinusoid(jnp.arange(F), D)[None].astype(x.dtype)
    x = ctx.cs(x, ("act_batch", "act_seq", "act_embed"))

    def body(x, bp):
        h = L.layernorm(x, bp["ln1"], cfg.norm_eps)
        att, _ = L.self_attention(bp["attn"], h, spec, causal=False, ctx=ctx)
        x = x + att
        h = L.layernorm(x, bp["ln2"], cfg.norm_eps)
        x = x + L.mlp_apply(bp["mlp"], h, cfg.gated_mlp, ctx=ctx)
        return x, None

    x, _ = loops.scan(body, x, params["enc_blocks"])
    return L.layernorm(x, params["enc_norm"], cfg.norm_eps)


def _dec_block(cfg, spec, bp, x, kc, vc, pos, kx, vx, *, ctx):
    h = L.layernorm(x, bp["ln1"], cfg.norm_eps)
    att, (kc, vc) = L.cached_attention(bp["attn"], h, spec, kc, vc, pos, ctx=ctx)
    x = x + att
    h = L.layernorm(x, bp["lnx"], cfg.norm_eps)
    x = x + L.cross_attention(bp["xattn"], h, spec, kx, vx)
    h = L.layernorm(x, bp["ln2"], cfg.norm_eps)
    x = x + L.mlp_apply(bp["mlp"], h, cfg.gated_mlp, ctx=ctx)
    return x, kc, vc


def encdec_init_cache(cfg: ArchConfig, B, max_len, dtype=jnp.bfloat16):
    hkv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    Ld = cfg.n_layers
    return {
        "k": jnp.zeros((Ld, B, max_len, hkv, hd), dtype),
        "v": jnp.zeros((Ld, B, max_len, hkv, hd), dtype),
        "k_mem": jnp.zeros((Ld, B, cfg.encoder_frames, hkv, hd), dtype),
        "v_mem": jnp.zeros((Ld, B, cfg.encoder_frames, hkv, hd), dtype),
    }


def encdec_cache_axes(cfg: ArchConfig):
    kv = ("layers", "act_batch", "act_cache", "act_kv", None)
    mem = ("layers", "act_batch", None, "act_kv", None)   # frames are short
    return {"k": kv, "v": kv, "k_mem": mem, "v_mem": mem}


def _embed_tokens(cfg, params, tokens, pos0):
    x = L.embed(params["embed"], tokens)
    pos0 = jnp.asarray(pos0)
    if pos0.ndim == 0:
        pos = pos0 + jnp.arange(tokens.shape[1])
        pe = _sinusoid(pos, cfg.d_model)[None]
    else:  # per-row positions (ragged serving batches)
        pos = pos0[:, None] + jnp.arange(tokens.shape[1])[None, :]
        pe = _sinusoid(pos, cfg.d_model)
    return x + pe.astype(x.dtype)


def encdec_forward_train(cfg, params, batch, *, ctx=NULL_CTX, remat=False):
    """batch: {'tokens': (B,S), 'frames': (B,F,D)}."""
    spec = _spec(cfg)
    mem = encode(cfg, params, batch["frames"], ctx=ctx)
    x = _embed_tokens(cfg, params, batch["tokens"], 0)
    x = ctx.cs(x, ("act_batch", "act_seq", "act_embed"))
    ckpt = (
        (lambda f: jax.checkpoint(f, policy=jax.checkpoint_policies.nothing_saveable))
        if remat
        else (lambda f: f)
    )

    @ckpt
    def body(x, bp):
        h = L.layernorm(x, bp["ln1"], cfg.norm_eps)
        att, _ = L.self_attention(bp["attn"], h, spec, causal=True, ctx=ctx)
        x = x + att
        h = L.layernorm(x, bp["lnx"], cfg.norm_eps)
        kx, vx = L.cross_kv(bp["xattn"], mem, spec)
        x = x + L.cross_attention(bp["xattn"], h, spec, kx, vx)
        h = L.layernorm(x, bp["ln2"], cfg.norm_eps)
        x = x + L.mlp_apply(bp["mlp"], h, cfg.gated_mlp, ctx=ctx)
        return x, None

    x, _ = loops.scan(body, x, params["dec_blocks"])
    if "targets" in batch:
        from repro.models.transformer import chunked_ce_loss

        loss_sum, n = chunked_ce_loss(cfg, params, x, batch["targets"], ctx=ctx)
        return loss_sum / n.astype(jnp.float32), {}
    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return L.logits_out(x, params["embed"], tied=True), {}


def encdec_prefill(cfg, params, batch, cache, *, ctx=NULL_CTX,
                   last_only: bool = False):
    spec = _spec(cfg)
    mem = encode(cfg, params, batch["frames"], ctx=ctx)
    x = _embed_tokens(cfg, params, batch["tokens"], 0)
    x = ctx.cs(x, ("act_batch", "act_seq", "act_embed"))

    def body(x, inp):
        bp, kc, vc = inp
        kx, vx = L.cross_kv(bp["xattn"], mem, spec)
        x, kc, vc = _dec_block(cfg, spec, bp, x, kc, vc, 0, kx, vx, ctx=ctx)
        return x, (kc, vc, kx, vx)

    x, (k, v, kx, vx) = loops.scan(
        body, x, (params["dec_blocks"], cache["k"], cache["v"])
    )
    cache = {
        "k": k,
        "v": v,
        "k_mem": kx.astype(cache["k_mem"].dtype),
        "v_mem": vx.astype(cache["v_mem"].dtype),
    }
    if last_only:
        x = x[:, -1:]
    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return L.logits_out(x, params["embed"], tied=True), cache


def encdec_cross_kv(cfg, params, frames, *, ctx=NULL_CTX):
    """Run the encoder and project per-decoder-layer cross-attention K/V —
    computed once at session open for the paged engine.  Returns (k, v) of
    shape (n_layers, B, F, Hkv, hd)."""
    spec = _spec(cfg)
    mem = encode(cfg, params, frames, ctx=ctx)

    def body(c, bp):
        return c, L.cross_kv(bp["xattn"], mem, spec)

    _, (k, v) = loops.scan(body, 0, params["dec_blocks"])
    return k, v


def encdec_decode_paged(
    cfg,
    params,
    tokens,            # (B, T) new tokens at positions base_lens[b] + t
    k_pages,           # (n_layers, n_pages, Hkv, P, hd) decoder self-attn KV
    v_pages,
    block_table,       # (B, n_max) int32
    base_lens,         # (B,) int32
    t_lens,            # (B,) int32 valid new tokens per row
    mem_cache,         # {'k_mem','v_mem'}: (n_layers, B, F, Hkv, hd)
    *,
    ctx=NULL_CTX,
):
    """Paged analogue of ``encdec_decode``: decoder self-attention runs over
    `PagedKV` pages, written in place through flat pools as in
    ``transformer.decode_paged``; cross-attention memory stays dense
    (bounded by encoder_frames, not part of the growing KV)."""
    spec = _spec(cfg)
    base_lens = jnp.asarray(base_lens, jnp.int32)
    t_lens = jnp.asarray(t_lens, jnp.int32)
    block_table = jnp.asarray(block_table, jnp.int32)
    x = _embed_tokens(cfg, params, tokens, base_lens)
    T = tokens.shape[1]
    n_layers, n_pages = k_pages.shape[:2]
    kf, vf = flat_pools(k_pages, v_pages)

    def body(carry, inp):
        x, kf, vf = carry
        bp, layer, kx, vx = inp
        h = L.layernorm(x, bp["ln1"], cfg.norm_eps)
        positions = base_lens[:, None] + jnp.arange(T)[None, :]
        q, k, v = L.qkv_proj(bp["attn"], h, spec, positions)
        o, kf, vf = write_and_attend(
            q, k, v, kf, vf, layer, n_pages, block_table, base_lens, t_lens,
            softcap=spec.softcap,
        )
        x = x + L.attn_out(bp["attn"], o)
        h = L.layernorm(x, bp["lnx"], cfg.norm_eps)
        x = x + L.cross_attention(bp["xattn"], h, spec, kx, vx)
        h = L.layernorm(x, bp["ln2"], cfg.norm_eps)
        x = x + L.mlp_apply(bp["mlp"], h, cfg.gated_mlp, ctx=ctx)
        return (x, kf, vf), None

    (x, kf, vf), _ = loops.scan(
        body, (x, kf, vf),
        (params["dec_blocks"], jnp.arange(n_layers, dtype=jnp.int32),
         mem_cache["k_mem"], mem_cache["v_mem"]),
    )
    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return L.logits_out(x, params["embed"], tied=True), (
        kf.reshape(k_pages.shape), vf.reshape(v_pages.shape))


def encdec_decode(cfg, params, tokens, cache, pos, *, ctx=NULL_CTX):
    spec = _spec(cfg)
    x = _embed_tokens(cfg, params, tokens, pos)

    def body(x, inp):
        bp, kc, vc, kx, vx = inp
        x, kc, vc = _dec_block(cfg, spec, bp, x, kc, vc, pos, kx, vx, ctx=ctx)
        return x, (kc, vc)

    x, (k, v) = loops.scan(
        body, x, (params["dec_blocks"], cache["k"], cache["v"],
                  cache["k_mem"], cache["v_mem"])
    )
    cache = dict(cache, k=k, v=v)
    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return L.logits_out(x, params["embed"], tied=True), cache
