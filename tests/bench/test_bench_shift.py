"""The sampled-token number: a served path whose log-probs lie within
``eps`` of the reference's reads a shift of at most ``eps`` on every
residual draw, also where ``p`` lies so close to ``q`` that the score's
own gap reads whole nats."""
import jax
import jax.numpy as jnp
import numpy as np

from bench import reference as R

V, ROWS, EPS = 512, 256, 0.02


def draws(seed: int, spread: float = 0.05):
    """Reference log-probs (at the default ``spread`` most tokens lie
    within a few percent of q = 1/V), a served copy off by at most ``EPS``
    per token, and the served path's residual draws under shared Gumbel
    keys."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    logp = jax.nn.log_softmax(spread * jax.random.normal(k1, (ROWS, V)), -1)
    off = jax.random.uniform(k2, (ROWS, V), minval=-EPS / 2, maxval=EPS / 2)
    served = jax.nn.log_softmax(logp + off, -1)
    g = jax.random.gumbel(k3, (ROWS, V))
    q = jnp.full((ROWS,), 1.0 / V)

    def score(lp):
        return jnp.log(jnp.maximum(jnp.exp(lp) - q[:, None], 1e-38)) + g

    tok = jnp.argmax(score(served), -1).astype(jnp.int32)
    ref = score(logp)
    gap = ref.max(-1) - jnp.take_along_axis(ref, tok[:, None], -1)[:, 0]
    return logp, g, q, tok, gap


def test_shift_is_bounded_where_the_gap_is_not():
    logp, g, q, tok, gap = draws(0)
    shift = np.asarray(jax.jit(R._shift)(logp, g, q, tok))
    assert shift.max() <= EPS + 1e-5
    assert float(gap.max()) > 10 * EPS


def test_shift_is_zero_for_the_reference_choice_and_grows_off_it():
    logp, g, q, _, _ = draws(1, spread=2.0)
    s = jnp.log(jnp.maximum(jnp.exp(logp) - q[:, None], 1e-38)) + g
    best = jnp.argmax(s, -1).astype(jnp.int32)
    assert float(jnp.max(R._shift(logp, g, q, best))) == 0.0
    # the token that each row's draw ranks tenth
    tenth = jnp.argsort(s, -1)[:, -10].astype(jnp.int32)
    assert float(jnp.min(R._shift(logp, g, q, tenth))) > 10 * EPS


def test_bonus_shift_is_half_the_gap():
    """Without q the score is log p + Gumbel, and a shift of d moves the
    gap between two tokens by 2d."""
    logp, g, _, _, _ = draws(2)
    zero = jnp.zeros(ROWS)
    s = logp + g
    second = jnp.argsort(s, -1)[:, -2].astype(jnp.int32)
    gap = s.max(-1) - jnp.take_along_axis(s, second[:, None], -1)[:, 0]
    np.testing.assert_allclose(np.asarray(R._shift(logp, g, zero, second)),
                               np.asarray(gap) / 2, rtol=1e-4, atol=1e-5)
