"""Device milliseconds per image of the ops under the LIF scans' scope
(``lif.scan``: every neuron of the stem, the blocks and the head)."""


def read(r):
    images = r.counts.get("images", 0)
    scoped = r.trace.scope_s("lif.")
    if not images or not scoped:
        return None
    return 1e3 * scoped / images
