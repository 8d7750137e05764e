"""The emulated edge's draft distribution and its wire statistics."""
import numpy as np

from bench.harness import compact_q


def test_uniform_q_compact_statistics():
    V, K, C = 1000, 6, 8
    draft, q = compact_q(K, C, V, np.random.default_rng(0))
    assert draft.shape == (K,) and ((draft >= 0) & (draft < V)).all()
    # exact log q at each drafted token: uniform over the vocabulary
    np.testing.assert_array_equal(q.logq_tok, np.float32(-np.log(V)))
    # a top-C table of distinct ids, each at 1/V, and the tail beyond it
    assert q.top_idx.shape == (K, C)
    assert all(len(set(r)) == C for r in q.top_idx)
    np.testing.assert_allclose(np.exp(q.top_logq), 1.0 / V, rtol=1e-6)
    np.testing.assert_allclose(q.tail + np.exp(q.top_logq).sum(-1), 1.0,
                               rtol=1e-6)
    # q is normalized: the tail spread over the other V - C ids is 1/V each
    np.testing.assert_allclose(q.tail / (V - C), 1.0 / V, rtol=1e-5)
