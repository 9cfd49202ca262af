"""Device-busy milliseconds per wave (one ``BatchedServer.step()``): the
busy time of the traced window over the waves run in it."""


def read(r):
    waves = r.counts.get("waves", 0)
    if not waves:
        return None
    return 1e3 * r.trace.busy_s / waves
