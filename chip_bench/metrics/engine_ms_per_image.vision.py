"""Device milliseconds per image of the ops under the engine's scopes
(``sparse_engine.*``, ``binary_engine.*``, ``dual_engine.*``)."""


def read(r):
    images = r.counts.get("images", 0)
    engine = r.trace.scope_s("sparse_engine.", "binary_engine.",
                             "dual_engine.")
    if not images or not engine:
        return None
    return 1e3 * engine / images
