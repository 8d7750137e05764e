"""The serving MoE layer: dropless grouped experts in both paged programs.

On seeded random float32 weights at a small size (8 routed experts, top-3,
2 shared, d_model 64), the served path is held to a plain reference: every
routed expert computed for every token in float32 at ``highest``
precision and masked by the routing weights, so nothing is ever dropped.
Tolerances are float32 rounding: the two sides sum the same terms in
another order (the k expert outputs, a dot's accumulation, attention over
pages against attention over one dense sequence).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build, transformer

E, K, SHARED = 8, 3, 2


def _cfg(norm_topk_prob=None):
    cfg = get_config("deepseek-moe-16b").reduced()
    moe = dataclasses.replace(cfg.moe, num_experts=E, top_k=K,
                              num_shared_experts=SHARED)
    if norm_topk_prob is not None:
        moe = dataclasses.replace(moe, norm_topk_prob=norm_topk_prob)
    return dataclasses.replace(cfg, moe=moe)


def _plain_moe(p, x, moe, norm):
    """Every routed expert for every token, masked by the routing weights,
    plus the shared experts' MLP: (..., D) float32."""
    x = x.astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        probs = jax.nn.softmax(x @ p["router"], axis=-1)
        top_w, top_e = jax.lax.top_k(probs, moe.top_k)
        if norm:
            top_w = top_w / top_w.sum(-1, keepdims=True)
        gate = (jax.nn.one_hot(top_e, moe.num_experts) * top_w[..., None]
                ).sum(-2)                                       # (..., E)
        y = jnp.zeros_like(x)
        for e in range(moe.num_experts):
            h = jax.nn.silu(x @ p["w_gate"][e]) * (x @ p["w_up"][e])
            y = y + gate[..., e:e + 1] * (h @ p["w_down"][e])
        s = p["shared"]
        return y + (jax.nn.silu(x @ s["gate"]) * (x @ s["up"])) @ s["down"]


def _plain_forward(monkeypatch, cfg, params, tokens, norm):
    """The full forward pass over whole sequences (dense causal attention)
    with the plain MoE in every layer: (B, S, V) logits."""
    def moe_apply(p, x, moe, **_):
        return _plain_moe(p, x, moe, norm).astype(x.dtype), {}

    with monkeypatch.context() as m:
        m.setattr(transformer, "moe_apply", moe_apply)
        logits, _ = build(cfg).forward_train(
            params, {"tokens": jnp.asarray(tokens, jnp.int32)})
    return np.asarray(logits, np.float32)


def _verify_on_pages(eng, cfg, params, slot, base, feed):
    """The paged program over ``feed`` at ``base`` of ``slot``, reading the
    engine's own pages: (1, len(feed), V) logits."""
    eng.kv.ensure_capacity(slot, base + len(feed))
    bt = eng.kv.block_table([slot], -(-(base + len(feed)) // eng.kv.page_size))
    logits, _ = jax.jit(lambda *a: transformer.decode_paged(cfg, params, *a))(
        jnp.asarray(feed[None]), eng.kv.k_pages, eng.kv.v_pages,
        jnp.asarray(bt), jnp.asarray([base]), jnp.asarray([len(feed)]))
    return logits


@pytest.mark.parametrize("norm", [True, False])
def test_serving_layer_matches_plain_reference(norm):
    """(a) The layer over a batch with pad positions: real tokens equal the
    plain reference; pad tokens get the shared experts alone."""
    from repro.models.moe import init_moe, moe_serve

    cfg = _cfg(norm)
    p = init_moe(jax.random.PRNGKey(0), cfg.d_model, cfg.d_ff, cfg.moe,
                 jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 5, cfg.d_model))
    valid = jnp.arange(5)[None, :] < jnp.asarray([5, 2, 0])[:, None]
    got = np.asarray(moe_serve(p, x, cfg.moe, valid))
    want = np.asarray(_plain_moe(p, x, cfg.moe, norm))
    v = np.asarray(valid)
    np.testing.assert_allclose(got[v], want[v], atol=1e-5, rtol=1e-5)
    s = p["shared"]
    with jax.default_matmul_precision("highest"):
        shared = (jax.nn.silu(x @ s["gate"]) * (x @ s["up"])) @ s["down"]
    np.testing.assert_allclose(got[~v], np.asarray(shared)[~v], atol=1e-5,
                               rtol=1e-5)


def test_norm_topk_prob_is_deepseeks_published_setting():
    assert get_config("deepseek-moe-16b").moe.norm_topk_prob is False
    assert get_config("grok-1-314b").moe.norm_topk_prob is True


def test_engine_prefill_then_verify_matches_full_forward(monkeypatch):
    """(b) A prompt prefilled in chunks through the paged engine, then a
    verify block run by the paged program over the engine's own pages,
    agree with the full forward pass: the first token, and the verify
    logits at every position."""
    from repro.serving import VerificationEngine, VerifyItem

    cfg = _cfg()
    params = build(cfg).init(jax.random.PRNGKey(2), dtype=jnp.float32)
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, cfg.vocab, 21).astype(np.int32)
    draft = rng.integers(0, cfg.vocab, 4).astype(np.int32)
    eng = VerificationEngine(cfg, params, max_slots=2, max_len=64,
                             method="greedy", page_size=8)
    st = eng.begin_prefill(prompt)
    while not st.finished:
        eng.prefill_chunk(st, 8)
    slot, first = st.slot, st.first_token

    feed = np.concatenate([[first], draft]).astype(np.int32)
    full = _plain_forward(monkeypatch, cfg, params,
                          np.concatenate([prompt, feed])[None],
                          cfg.moe.norm_topk_prob)[0]
    assert first == int(np.argmax(full[len(prompt) - 1]))

    base = len(prompt)
    logits = _verify_on_pages(eng, cfg, params, slot, base, feed)
    # float32; pages against one dense sequence sum attention in another
    # order, and 2 layers compound it
    np.testing.assert_allclose(np.asarray(logits[0]), full[base:],
                               atol=1e-4, rtol=1e-4)
    (out,) = eng.verify([VerifyItem(slot=slot, draft_tokens=draft)])
    want = full[base:].argmax(-1)
    n = int(np.argmin(np.append(draft == want[:-1], False)))
    assert (out.accept_len, out.token) == (n, int(want[n]))


def test_overflowing_chunk_is_not_dropped(monkeypatch):
    """(c) One 64-token prefill chunk of a repeated token sends all 64 to
    the same experts, more than GShard capacity keeps (30 at E=8, k=3,
    factor 1.25).  Prefilled in one chunk through the engine, its first
    token and the pages it wrote, read by a verify block, still agree with
    the dropless reference at every position."""
    from repro.serving import VerificationEngine

    cfg = _cfg()
    params = build(cfg).init(jax.random.PRNGKey(4), dtype=jnp.float32)
    T = 64
    prompt = np.full(T, 7, np.int32)
    capacity = int(round(T * K / E * cfg.moe.capacity_factor))
    # every position has the same hidden state, so one expert takes all T
    assert T > capacity == 30
    eng = VerificationEngine(cfg, params, max_slots=1, max_len=96,
                             method="greedy", page_size=8)
    st = eng.begin_prefill(prompt)
    eng.prefill_chunk(st, T)
    assert st.finished
    feed = np.asarray([st.first_token, 3, 5], np.int32)
    want = _plain_forward(monkeypatch, cfg, params,
                          np.concatenate([prompt, feed])[None],
                          cfg.moe.norm_topk_prob)[0]
    assert st.first_token == int(np.argmax(want[T - 1]))
    logits = _verify_on_pages(eng, cfg, params, st.slot, T, feed)
    np.testing.assert_allclose(np.asarray(logits[0]), want[T:], atol=1e-4,
                               rtol=1e-4)


def test_verify_row_is_composition_independent():
    """(d) A row's verify logits are the same alone and in a batch of 8
    with other live rows and pad rows (zero block table, zero length)."""
    cfg = _cfg()
    params = build(cfg).init(jax.random.PRNGKey(5), dtype=jnp.float32)
    rng = np.random.default_rng(6)
    P, n_pages, T = 4, 33, 7
    pool = (cfg.n_layers, n_pages, cfg.n_kv_heads, P, cfg.resolved_head_dim)
    kp = jnp.asarray(rng.normal(size=pool), jnp.float32)
    vp = jnp.asarray(rng.normal(size=pool), jnp.float32)
    tokens = rng.integers(0, cfg.vocab, (8, T)).astype(np.int32)
    bt = np.zeros((8, 4), np.int32)
    bt[:5] = 1 + np.arange(20).reshape(5, 4)
    base = np.asarray([6, 3, 1, 8, 5, 0, 0, 0], np.int32)
    tl = np.asarray([7, 7, 4, 2, 7, 0, 0, 0], np.int32)
    run = jax.jit(lambda *a: transformer.decode_paged(cfg, params, *a)[0])
    alone = run(tokens[:1], kp, vp, bt[:1], base[:1], tl[:1])
    batch = run(tokens, kp, vp, bt, base, tl)
    np.testing.assert_allclose(np.asarray(alone[0]), np.asarray(batch[0]),
                               atol=1e-4, rtol=1e-4)


def test_moe_counters_count_tokens_and_passes():
    """(e) ``moe_assignments`` = real tokens x k x layers and
    ``moe_expert_passes`` = program calls x layers, for a ragged prefill
    call and a verify call."""
    from repro.serving import VerificationEngine, VerifyItem
    from repro.serving.engine import PrefillChunkItem

    cfg = _cfg()
    params = build(cfg).init(jax.random.PRNGKey(7), dtype=jnp.float32)
    eng = VerificationEngine(cfg, params, max_slots=4, max_len=64,
                             method="greedy", page_size=8)
    L = cfg.n_layers
    assert eng.stats["moe_assignments"] == eng.stats["moe_expert_passes"] == 0
    states = [eng.begin_prefill(np.arange(a, a + n, dtype=np.int32))
              for a, n in ((2, 9), (40, 5))]
    eng.step([PrefillChunkItem(states[0], 6), PrefillChunkItem(states[1], 5)])
    assert eng.stats["moe_assignments"] == (6 + 5) * K * L
    assert eng.stats["moe_expert_passes"] == 1 * L
    eng.step([PrefillChunkItem(states[0], 3)])
    items = [VerifyItem(slot=s.slot, draft_tokens=np.asarray(d, np.int32))
             for s, d in zip(states, ([3, 4, 5], [6]))]
    eng.verify(items)
    assert eng.stats["moe_assignments"] == (6 + 5 + 3 + 4 + 2) * K * L
    assert eng.stats["moe_expert_passes"] == 3 * L
