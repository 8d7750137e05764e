"""Tokens committed in the window (first tokens and verdicts), over the
window."""


def committed_in_window(w, s) -> int:
    n = int(w.in_window(s.t_first)) if s.first_token >= 0 else 0
    return n + sum(k for t, k in s.commits if w.in_window(t))


def read(w):
    return sum(committed_in_window(w, s) for s in w.sessions.values()) \
        / w.seconds
