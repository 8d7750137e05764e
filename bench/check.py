"""The comparison that decides ``correct``.

After the window, a sample of the sessions the run finished (drawn from
the seed, the longest among them) is replayed through the plain
reference, one forward over each session's prompt and committed tokens.
Every decision the served path made is then judged against the
reference's float32 numbers, with the same random draws the program keys
by (session id, committed length) from the engine seed.  One number is
compared, ``decision_shift_max``: over all decisions, the widest shift of
the reference's log-probs (nats, every token's moved by at most it) under
which the reference decides as the program did.  Per kind of decision:

* ``accept_margin_max``: an accept test, ``log p - log q - log u >= 0``,
  the margin by which it went the wrong way: an accepted draft token
  whose reference margin is below 0, or the rejected one above it.
* ``sample_shift_max``: a sampled correction or bonus token, the shift
  under which it wins the row's draw (`reference._shift`): sampling is the
  argmax of ``log(residual) + Gumbel``, residual ``max(p - q, 0)``.  The
  score's own gap, ``sample_gap_max``, grows without bound as ``p`` nears
  ``q``, so a token drawn there reads whole nats from a path whose
  log-probs lie within hundredths of the reference's.
* ``greedy_gap_max``: a session's first token (greedy, from prefill), the
  gap of its logit below the reference's best; a shift of half of it
  closes it.

All in nats; the parts and the sampled gap are logged.  The control is
the reference in int8 (W8A8) put in the program's place: its own
decisions at the same rows, judged the same way.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference as R

NUMBERS = ("decision_shift_max",)


def sample(w, seed: int, n: int) -> list:
    """Finished sessions to compare: the one with the most generated
    tokens, then others drawn from the seed."""
    done = sorted((s for s in w.sessions.values() if s.done),
                  key=lambda s: s.plan.sid)
    if not done:
        return []
    longest = max(done, key=lambda s: len(s.gen))
    rest = [s for s in done if s is not longest]
    rng = np.random.default_rng([seed, 11])
    pick = rng.permutation(len(rest))[: max(0, n - 1)]
    return [longest] + [rest[i] for i in sorted(pick)]


@jax.jit
def _keys(base, sid, fed):
    row = jax.vmap(lambda a, b: jax.random.fold_in(
        jax.random.fold_in(base, a), b))(sid, fed)
    u = jax.vmap(lambda k: jax.vmap(lambda i: jax.random.uniform(
        jax.random.fold_in(k, i), ()))(jnp.arange(2, 2 + 8)))(row)
    corr = jax.vmap(lambda k: jax.random.fold_in(k, 1))(row)
    return u, corr


def rows_of(sessions: list, engine_seed: int) -> tuple[list, list]:
    """Per session: the reference rows to score, and what each decided.

    Accept rows (``mode`` 0) carry the draft token the row's accept test
    read, its uniform ``u`` and whether the program accepted it.  Choice
    rows carry the program's chosen token (``mode`` 1 residual correction,
    2 bonus, 3 greedy first token) and the Gumbel key of the draw."""
    base = jax.random.PRNGKey(engine_seed)
    rows, decided = [], []
    for s in sessions:
        P0 = len(s.prompt)
        pos, x_tok, mode, key, tok, acc, logu = [P0 - 1], [-1], [3], \
            [np.zeros(2, np.uint32)], [s.first_token], [False], [0.0]
        done = [r for r in s.rounds if r.accept_len >= 0]
        if done:
            u, ck = _keys(base, jnp.asarray([s.plan.sid] * len(done), jnp.int32),
                          jnp.asarray([r.fed for r in done], jnp.int32))
            u, ck = np.asarray(u), np.asarray(ck)
        for j, r in enumerate(done):
            K, L = len(r.draft), r.accept_len
            for i in range(min(L + 1, K)):
                pos.append(r.fed + i)
                x_tok.append(int(r.draft[i]))
                mode.append(0)
                key.append(np.zeros(2, np.uint32))
                tok.append(-1)
                acc.append(i < L)
                logu.append(float(np.log(np.float32(u[j, i]))))
            pos.append(r.fed + L)
            x_tok.append(-1)
            mode.append(1 if L < K else 2)
            key.append(ck[j])
            tok.append(r.token)
            acc.append(False)
            logu.append(0.0)
        rows.append({"pos": np.asarray(pos, np.int32),
                     "x_tok": np.asarray(x_tok, np.int32),
                     "mode": np.asarray(mode, np.int32),
                     "key": np.asarray(key, np.uint32),
                     "probe": np.full((len(pos), 2), -1, np.int32)})
        rows[-1]["probe"][:, 0] = tok
        decided.append({"acc": np.asarray(acc), "logu": np.asarray(logu)})
    return rows, decided


def _violations(c, ref, rows, decided, accepted_by, chosen_col):
    """Per number, the widest violation of the decisions given by
    ``accepted_by`` (accept rows) and probe column ``chosen_col``."""
    logq = np.float32(-np.log(R.dims(c)["V"]))
    mode = np.concatenate([r["mode"] for r in rows])
    logu = np.concatenate([d["logu"] for d in decided])
    m = ref["lp_x"] - logq - logu
    acc_rows = mode == 0
    acc_v = np.where(accepted_by, -m, m)[acc_rows]
    gap = ref["zmax"] - ref["zprobe"][:, chosen_col]
    shift = ref["shift"][:, chosen_col]
    sampled = (mode == 1) | (mode == 2)
    out = {"accept_margin_max": float(np.max(np.maximum(acc_v, 0.0),
                                             initial=0.0)),
           "sample_shift_max": float(np.max(shift[sampled], initial=0.0)),
           "greedy_gap_max": float(np.max(gap[mode == 3], initial=0.0)),
           "sample_gap_max": float(np.max(gap[sampled], initial=0.0))}
    out["decision_shift_max"] = max(out["accept_margin_max"],
                                    out["sample_shift_max"],
                                    out["greedy_gap_max"] / 2)
    if sampled.any():
        # the rows that read the widest gap and the widest shift
        idx = np.flatnonzero(sampled)
        V = R.dims(c)["V"]
        for name, i in (("gap_row", idx[np.argmax(gap[idx])]),
                        ("shift_row", idx[np.argmax(shift[idx])])):
            out[name] = {"mode": int(mode[i]), "gap": float(gap[i]),
                         "shift": float(shift[i]),
                         "p_tok_times_V": float(
                             np.exp(ref["lp_probe"][i, chosen_col]) * V)}
    return out


def compare(c: dict, seed: int, engine_seed: int, sessions: list,
            control: bool = False, pad_to: int = 0) -> dict:
    """The readings of the program (and of the control, if asked) against
    the reference, plus what was compared."""
    rows, decided = rows_of(sessions, engine_seed)
    seqs = [np.concatenate([s.prompt, np.asarray(s.gen, np.int32)])
            for s in sessions]
    out = {"sessions": len(sessions),
           "served_tokens": int(sum(len(s.gen) for s in sessions)),
           "decisions": int(sum(len(r["pos"]) for r in rows))}
    if control:
        h = R.hidden_states(c, seed, seqs, quant="int8", pad_to=pad_to)
        ctl = R.row_stats(c, seed, h, rows, quant="int8")
        del h
        logq = np.float32(-np.log(R.dims(c)["V"]))
        logu = np.concatenate([d["logu"] for d in decided])
        ctl_acc = (ctl["lp_x"] - logq - logu) >= 0
        i = 0
        for r in rows:
            n = len(r["pos"])
            r["probe"][:, 1] = ctl["zarg"][i:i + n]
            i += n
    h = R.hidden_states(c, seed, seqs, pad_to=pad_to)
    ref = R.row_stats(c, seed, h, rows)
    del h
    prog_acc = np.concatenate([d["acc"] for d in decided])
    out["program"] = _violations(c, ref, rows, decided, prog_acc, 0)
    if control:
        out["control"] = _violations(c, ref, rows, decided, ctl_acc, 1)
    return out
