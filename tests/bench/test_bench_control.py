"""The control: the reference in int8 put in the program's place.

At this size the int8 control's errors are far smaller than at the
configurations' widths, so the test plants decisions on a knife's edge:
each session's first draft token sits 1e-4 nats on the rejecting side of
its accept test, and its correction is the reference's own choice.  A
program deciding as the reference does reads 0; the int8 control flips
edge decisions and reads above 0 on the same rows, judged the same way.
"""
import jax
import jax.numpy as jnp
import numpy as np

from bench import check as CK
from bench import reference as R
from bench import traffic as TR
from bench.harness import Round, Session
from bench_tiny import CONFIG

SEED, ENGINE_SEED, K = 123, 45, 6


def knife_edge_sessions(n: int) -> list:
    c, d = CONFIG, R.dims(CONFIG)
    V = d["V"]
    top = R.top_weights(c, SEED, None)
    rng = np.random.default_rng(0)
    logq = np.float32(-np.log(V))
    out = []
    for sid in range(n):
        prompt = rng.integers(2, V, 24).astype(np.int32)
        h = R.hidden_states(c, SEED, [prompt])[0]
        lg = np.asarray(R._rms(h, top["final_norm"], d["eps"])
                        @ top["unembed"])
        first = int(np.argmax(lg[-1]))
        seq = np.concatenate([prompt, [first]]).astype(np.int32)
        h = R.hidden_states(c, SEED, [seq])[0]
        lg = R._rms(h, top["final_norm"], d["eps"]) @ top["unembed"]
        logp = np.asarray(jax.nn.log_softmax(lg[-1]))
        fed = len(seq) - 1
        u, ck = CK._keys(jax.random.PRNGKey(ENGINE_SEED),
                         jnp.asarray([sid], jnp.int32),
                         jnp.asarray([fed], jnp.int32))
        logu = float(np.log(np.float32(np.asarray(u)[0, 0])))
        margin = logp - logq - logu
        x1 = int(np.argmin(np.where(margin < -1e-4, -margin, np.inf)))
        p = np.exp(logp)
        g = np.asarray(jax.random.gumbel(np.asarray(ck)[0], (V,),
                                         jnp.float32))
        tok = int(np.argmax(np.log(np.maximum(p - 1.0 / V, 1e-38)) + g))
        draft = np.concatenate([[x1], rng.integers(0, V, K - 1)]).astype(
            np.int32)
        plan = TR.SessionPlan(sid, 0.0, 0, 24, 1, {})
        s = Session(plan, prompt, rng, first_token=first, gen=[first, tok])
        s.rounds = [Round(0.0, fed, draft, accept_len=0, token=tok)]
        s.done = True
        out.append(s)
    return out


def test_int8_control_fails_where_the_reference_passes():
    sessions = knife_edge_sessions(12)
    got = CK.compare(CONFIG, SEED, ENGINE_SEED, sessions, control=True)
    prog, ctl = got["program"], got["control"]
    assert prog["accept_margin_max"] < 1e-3
    assert prog["sample_shift_max"] < 1e-3 and prog["greedy_gap_max"] < 1e-3
    assert ctl["accept_margin_max"] > prog["accept_margin_max"]
    assert ctl["decision_shift_max"] > prog["decision_shift_max"]
    assert got["decisions"] == 12 * 3
