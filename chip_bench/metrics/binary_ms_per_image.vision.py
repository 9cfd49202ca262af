"""Device milliseconds per image of the ops under the binary engine's
scopes (``binary_engine.*``: scores, binarize and context of attention)."""


def read(r):
    images = r.counts.get("images", 0)
    scoped = r.trace.scope_s("binary_engine.")
    if not images or not scoped:
        return None
    return 1e3 * scoped / images
