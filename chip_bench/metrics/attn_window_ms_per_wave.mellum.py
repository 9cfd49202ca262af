"""Device milliseconds per wave of the ops under ``attn.window``: the
sliding-window layers' attention half (norm, Q/K/V, RoPE, cache write,
attention, W_o)."""


def read(r):
    waves = r.counts.get("waves", 0)
    scoped = r.trace.scope_s("attn.window")
    if not waves or not scoped:
        return None
    return 1e3 * scoped / waves
