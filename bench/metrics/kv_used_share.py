"""Pages in use over the pool, time-averaged over the window (the
allocator's ``pages_in_use`` after each step, held until the next)."""


def read(w):
    pts = [(s.t1, s.pages_in_use) for s in w.steps]
    area = dur = 0.0
    for (t, n), (t2, _) in zip(pts, pts[1:] + [(w.w1, 0)]):
        a, b = max(t, w.w0), min(t2, w.w1)
        if b > a:
            area += n * (b - a)
            dur += b - a
    return 100.0 * area / dur / w.n_pages if dur else None
