"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs pure-jnp ref."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import backend
from repro.kernels.logit_features.ops import logit_features_op, logit_features_ref
from repro.kernels.paged_attention.ops import (
    gather_pages,
    paged_attention_op,
    paged_attention_ref,
    scatter_kv_pages,
)
from repro.kernels.verify_attention.ops import (
    verify_attention_op,
    verify_attention_ref,
)

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _tol(dtype):
    return TOL[jnp.bfloat16] if dtype == jnp.bfloat16 else TOL[jnp.float32]


# ---------------------------------------------------------------------------
# verify attention (small-Q x long-KV online softmax)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,Hq,Hkv,K,S,D", [
    (1, 4, 4, 1, 128, 64),       # plain decode, MHA
    (2, 4, 2, 8, 256, 64),       # GQA verify block
    (3, 8, 1, 5, 384, 128),      # MQA, ragged lengths
    (2, 4, 2, 16, 1024, 128),    # long prefix
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_verify_attention_sweep(B, Hq, Hkv, K, S, D, dtype):
    rng = np.random.default_rng(42)
    q = jnp.asarray(rng.normal(size=(B, K, Hq, D)), dtype)
    k = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), dtype)
    v = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), dtype)
    lengths = jnp.asarray(rng.integers(K + 1, S + 1, size=B), jnp.int32)
    out = verify_attention_op(q, k, v, lengths, blk_kv=128)
    ref = verify_attention_ref(q, k, v, lengths)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=_tol(dtype), rtol=_tol(dtype),
    )


def test_verify_attention_softcap_and_window():
    rng = np.random.default_rng(0)
    B, Hq, Hkv, K, S, D = 2, 4, 2, 4, 256, 64
    q = jnp.asarray(rng.normal(size=(B, K, Hq, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)
    lengths = jnp.asarray([200, 256], jnp.int32)
    for kw in ({"softcap": 30.0}, {"window": 64}, {"softcap": 50.0, "window": 128}):
        out = verify_attention_op(q, k, v, lengths, blk_kv=128, **kw)
        ref = verify_attention_ref(q, k, v, lengths, **kw)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_verify_attention_masking_is_exact():
    """Tokens beyond `lengths` must not leak into the output."""
    rng = np.random.default_rng(1)
    B, Hq, Hkv, K, S, D = 1, 2, 2, 2, 256, 32
    q = jnp.asarray(rng.normal(size=(B, K, Hq, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)
    lengths = jnp.asarray([100], jnp.int32)
    out1 = verify_attention_op(q, k, v, lengths)
    # poison the masked region
    k2 = k.at[:, 100:].set(1e4)
    v2 = v.at[:, 100:].set(-1e4)
    out2 = verify_attention_op(q, k2, v2, lengths)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), atol=1e-6)


# ---------------------------------------------------------------------------
# paged attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,Hq,Hkv,D,page,n_pages,n_max", [
    (2, 4, 2, 64, 128, 8, 4),
    (4, 8, 8, 64, 256, 16, 3),
    (1, 8, 1, 128, 128, 4, 4),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_sweep(B, Hq, Hkv, D, page, n_pages, n_max, dtype):
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(size=(B, Hq, D)), dtype)
    kp = jnp.asarray(rng.normal(size=(n_pages, Hkv, page, D)), dtype)
    vp = jnp.asarray(rng.normal(size=(n_pages, Hkv, page, D)), dtype)
    bt = jnp.asarray(
        rng.permutation(n_pages)[: B * n_max].reshape(B, n_max), jnp.int32
    )
    lengths = jnp.asarray(rng.integers(1, n_max * page + 1, size=B), jnp.int32)
    out = paged_attention_op(q, kp, vp, bt, lengths)
    ref = paged_attention_ref(q, kp, vp, bt, lengths)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=_tol(dtype), rtol=_tol(dtype),
    )


def test_paged_matches_dense_attention():
    """Paged result == dense attention over the gathered pages."""
    rng = np.random.default_rng(3)
    B, H, Hkv, D, page, n_pages, n_max = 2, 4, 2, 64, 128, 6, 3
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(n_pages, Hkv, page, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(n_pages, Hkv, page, D)), jnp.float32)
    bt = jnp.asarray([[0, 2, 4], [1, 3, 5]], jnp.int32)
    lengths = jnp.asarray([300, 384], jnp.int32)
    out = paged_attention_op(q, kp, vp, bt, lengths)
    kd = gather_pages(kp, bt)
    vd = gather_pages(vp, bt)
    ref = verify_attention_ref(
        q[:, None], kd, vd, lengths
    )[:, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_scatter_kv_pages_writes_head_major_pages():
    """Token t of row b lands at [page, head, offset] of the head-major
    pool; positions past t_lens go to scratch page 0 and nowhere else.
    In a flat pool of two layers, ``page_offset`` moves every write, the
    scratch page's included, into layer 1's pages and leaves layer 0's."""
    rng = np.random.default_rng(5)
    n_pages, Hkv, page, D, T = 6, 2, 4, 8, 3
    k_new = jnp.asarray(rng.normal(size=(2, T, Hkv, D)), jnp.float32)
    bt = jnp.asarray([[3, 5], [2, 4]], jnp.int32)
    base = jnp.asarray([3, 1], jnp.int32)            # row 0 crosses a page
    tl = jnp.asarray([3, 2], jnp.int32)              # row 1: last is padding
    for n_layers, layer in ((1, 0), (2, 0), (2, 1)):
        kp = jnp.zeros((n_layers * n_pages, Hkv, page, D), jnp.float32)
        kp2, vp2 = scatter_kv_pages(kp, kp, k_new, -k_new, bt, base, tl,
                                    jnp.int32(layer * n_pages))
        want = np.zeros((n_layers, n_pages, Hkv, page, D), np.float32)
        for b, t, pid, off in [(0, 0, 3, 3), (0, 1, 5, 0), (0, 2, 5, 1),
                               (1, 0, 2, 1), (1, 1, 2, 2)]:
            want[layer, pid, :, off] = np.asarray(k_new[b, t])
        got = np.asarray(kp2).reshape(want.shape)
        vgot = np.asarray(vp2).reshape(want.shape)
        live = [l for l in range(n_layers) if l != layer]
        np.testing.assert_array_equal(got[live], want[live])
        np.testing.assert_array_equal(got[layer, 1:], want[layer, 1:])
        np.testing.assert_array_equal(vgot[layer, 1:], -want[layer, 1:])
        # the padded token landed on the layer's scratch page, offset 0
        np.testing.assert_array_equal(got[layer, 0, :, 0],
                                      np.asarray(k_new[1, 2]))


@pytest.mark.parametrize("platform,expected", [
    ("cpu", True), ("tpu", False), ("gpu", RuntimeError),
])
def test_interpret_mode_only_on_cpu(monkeypatch, platform, expected):
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    if expected is RuntimeError:
        with pytest.raises(RuntimeError, match="gpu"):
            backend.interpret()
    else:
        assert backend.interpret() is expected


# ---------------------------------------------------------------------------
# logit features
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,V", [(1, 128), (4, 1000), (2, 4096), (8, 50304)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_logit_features_sweep(B, V, dtype):
    rng = np.random.default_rng(11)
    logits = jnp.asarray(rng.normal(size=(B, V)) * 3, dtype)
    out = logit_features_op(logits)
    ref = logit_features_ref(logits)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=3e-2 if dtype == jnp.bfloat16 else 1e-5
    )


def test_logit_features_values():
    """Hand-checkable case: uniform logits."""
    V = 64
    logits = jnp.zeros((1, V), jnp.float32)
    f = np.asarray(logit_features_ref(logits))[0]
    assert abs(f[0] - 1.0 / V) < 1e-6          # confidence
    assert abs(f[1] - 1.0) < 1e-6              # normalized entropy = 1
    assert abs(f[2] - 0.0) < 1e-6              # margin
    assert abs(f[3] - 0.0) < 1e-6              # logit std
    assert abs(f[4] - 8.0 / V) < 1e-6          # top-8 mass
