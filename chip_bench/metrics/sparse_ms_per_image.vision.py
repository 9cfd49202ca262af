"""Device milliseconds per image of the ops under the sparse engine's
scopes (``sparse_engine.*``: the Q/K/V, wo, w1 and w2 projections)."""


def read(r):
    images = r.counts.get("images", 0)
    scoped = r.trace.scope_s("sparse_engine.")
    if not images or not scoped:
        return None
    return 1e3 * scoped / images
