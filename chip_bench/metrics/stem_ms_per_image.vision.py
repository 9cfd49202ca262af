"""Device milliseconds per image of the ops under the SPS stem's scope
(``sps.stem``: the four convs with their BatchNorm, LIF and pools)."""


def read(r):
    images = r.counts.get("images", 0)
    scoped = r.trace.scope_s("sps.")
    if not images or not scoped:
        return None
    return 1e3 * scoped / images
