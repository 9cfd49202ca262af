"""Device milliseconds per wave of the ops under the expert layer's scopes
(``moe.*``: router, dispatch, grouped matmuls, combine), every layer."""


def read(r):
    waves = r.counts.get("waves", 0)
    scoped = r.trace.scope_s("moe.")
    if not waves or not scoped:
        return None
    return 1e3 * scoped / waves
