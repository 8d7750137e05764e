"""Mean verify rows per server step that ran a verify batch, in the
window (the server's ``last_served`` after each step)."""
from bench.metrics._common import verify_steps


def read(w):
    steps = verify_steps(w)
    return sum(len(s.verify_rows) for s in steps) / len(steps) if steps \
        else None
