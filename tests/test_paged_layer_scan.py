"""The paged layer scan writes the stacked KV pools in place.

``decode_paged`` (dense, MoE, vlm) and ``encdec_decode_paged`` (audio)
carry the pools through their layer scan as one flat page pool each and
address layer ``l`` as ``block_table + l * n_pages``.  Against a
layer-by-layer reference (each layer's 4-D slice cut out of the stack, the
new K/V scattered into it, attended from it, and written back), logits of
the live rows and every layer's live pages must be bit-identical, and the
scan must hand the layers their indices in order.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common import loops
from repro.configs import get_config
from repro.kernels.paged_attention.ops import paged_verify_attention_op
from repro.models import encdec, transformer
from repro.models.zoo import build

PAGE, N_PAGES, T = 4, 9, 5
# three rows: one whose write crosses a page boundary (positions 6..10 over
# pages 3, 5, 7), one with t_lens < T over ragged base_lens, one padded
BLOCK_TABLE = [[1, 3, 5, 7], [2, 4, 6, 8], [0, 0, 0, 0]]
BASE_LENS = [6, 1, 0]
T_LENS = [5, 3, 0]
LIVE = 2


def _scatter_per_layer(kp, vp, k, v, bt, base, tl):
    """One layer's pages (n_pages, Hkv, P, D): token (b, t) to page
    bt[b, (base+t)//P], offset (base+t)%P; masked tokens to page 0."""
    n_max = bt.shape[1]
    pos = base[:, None] + jnp.arange(T)[None, :]
    valid = jnp.arange(T)[None, :] < tl[:, None]
    pid = jnp.take_along_axis(bt, jnp.clip(pos // PAGE, 0, n_max - 1), 1)
    pid = jnp.where(valid, pid, 0)
    off = jnp.where(valid, pos % PAGE, 0)
    return (kp.at[pid, :, off].set(k.astype(kp.dtype)),
            vp.at[pid, :, off].set(v.astype(vp.dtype)))


def _layer_by_layer(n_layers):
    """A ``write_and_attend`` without the flat addressing: it cuts layer
    ``layer``'s slice out of the stack, writes it, attends from it and
    puts it back."""

    def write_and_attend(q, k, v, kf, vf, layer, n_pages, bt, base, tl, *,
                         softcap=0.0):
        k5 = kf.reshape(n_layers, N_PAGES, *kf.shape[1:])
        v5 = vf.reshape(n_layers, N_PAGES, *vf.shape[1:])
        kl = jax.lax.dynamic_index_in_dim(k5, layer, keepdims=False)
        vl = jax.lax.dynamic_index_in_dim(v5, layer, keepdims=False)
        kl, vl = _scatter_per_layer(kl, vl, k, v, bt, base, tl)
        o = paged_verify_attention_op(q, kl, vl, bt, base, softcap=softcap)
        put = lambda p5, pl: jax.lax.dynamic_update_index_in_dim(
            p5, pl, layer, 0).reshape(kf.shape)
        return o, put(k5, kl), put(v5, vl)

    return write_and_attend


def _case(name, dropless):
    cfg = get_config(name).reduced()
    params = build(cfg).init(jax.random.PRNGKey(0), dtype=jnp.float32)
    rng = np.random.default_rng(1)
    B = len(BLOCK_TABLE)
    pool = (cfg.n_layers, N_PAGES, cfg.n_kv_heads, PAGE,
            cfg.resolved_head_dim)
    kp = jnp.asarray(rng.normal(size=pool), jnp.float32)
    vp = jnp.asarray(rng.normal(size=pool), jnp.float32)
    args = (jnp.asarray(rng.integers(0, cfg.vocab, (B, T)), jnp.int32),
            kp, vp, jnp.asarray(BLOCK_TABLE, jnp.int32),
            jnp.asarray(BASE_LENS, jnp.int32), jnp.asarray(T_LENS, jnp.int32))
    if cfg.family == "audio":
        frames = jax.random.normal(
            jax.random.PRNGKey(2), (B, cfg.encoder_frames, cfg.d_model))
        k_mem, v_mem = encdec.encdec_cross_kv(cfg, params, frames)
        fn = lambda *a: encdec.encdec_decode_paged(
            cfg, params, *a, {"k_mem": k_mem, "v_mem": v_mem})
        return cfg, encdec, fn, args
    cross = None
    if cfg.family == "vlm":
        emb = jax.random.normal(
            jax.random.PRNGKey(2), (B, cfg.num_image_tokens, cfg.d_model))
        k_img, v_img = transformer.vlm_cross_kv(cfg, params, emb)
        cross = {"k_img": k_img, "v_img": v_img}
    fn = lambda *a: transformer.decode_paged(
        cfg, params, *a, cross, dropless=dropless)
    return cfg, transformer, fn, args


CASES = pytest.mark.parametrize("name,dropless", [
    ("qwen2-7b", True),
    ("deepseek-moe-16b", True),
    ("deepseek-moe-16b", False),       # the prefill program's routing
    ("llama-3.2-vision-90b", True),
    ("whisper-tiny", True),
], ids=["dense", "moe", "moe-prefill", "vlm", "audio"])


@CASES
def test_paged_layer_scan_matches_layer_by_layer(monkeypatch, name, dropless):
    cfg, module, fn, args = _case(name, dropless)
    logits, (kp, vp) = jax.jit(fn)(*args)
    monkeypatch.setattr(module, "write_and_attend",
                        _layer_by_layer(cfg.n_layers))
    want_logits, (want_kp, want_vp) = jax.jit(lambda *a: fn(*a))(*args)

    assert kp.shape == vp.shape == args[1].shape
    for t_len, got, want in zip(T_LENS[:LIVE], logits, want_logits):
        np.testing.assert_array_equal(np.asarray(got[:t_len]),
                                      np.asarray(want[:t_len]))
    for got, want, old in ((kp, want_kp, args[1]), (vp, want_vp, args[2])):
        got, want = np.asarray(got)[:, 1:], np.asarray(want)[:, 1:]
        np.testing.assert_array_equal(got, want)
        # the new tokens did land: every layer's live pages changed
        assert (got != np.asarray(old)[:, 1:]).any(axis=(1, 2, 3, 4)).all()


@CASES
def test_paged_layer_scan_visits_layers_in_order(monkeypatch, name, dropless):
    """Unrolled and run eagerly, the scan's i-th layer gets index i (the
    vlm scans its groups, and the self-attention layers inside each)."""
    cfg, module, fn, args = _case(name, dropless)
    seen = []
    real = module.write_and_attend

    def spy(q, k, v, kf, vf, layer, *rest, **kw):
        seen.append(int(layer))
        return real(q, k, v, kf, vf, layer, *rest, **kw)

    monkeypatch.setattr(module, "write_and_attend", spy)
    with loops.cost_unroll():
        fn(*args)
    assert seen == list(range(cfg.n_layers))
