"""Device milliseconds per wave of the ops under ``attn.full``: the
full-attention layers' attention half (norm, Q/K/V, YaRN RoPE, cache
write, attention over the whole slot, W_o)."""


def read(r):
    waves = r.counts.get("waves", 0)
    scoped = r.trace.scope_s("attn.full")
    if not waves or not scoped:
        return None
    return 1e3 * scoped / waves
