"""Process start to the first due event: loading, weights, pool sizing,
warm-up (compilation where the cache is cold) and set-up prefill."""


def read(w):
    return w.setup_s
