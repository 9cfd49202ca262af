"""Milliseconds per wave (one ``BatchedServer.step()``, a ``bench.wave``
span) in which the device runs nothing while the host admits, schedules,
copies and samples: the waves' total span less the device's busy time,
over the waves. Totals, since the trace puts host and device on one clock
only to about a millisecond; the device runs only inside waves."""


def read(r):
    waves = [s[2] for s in r.trace.spans if s[0] == "bench.wave"]
    if not waves:
        return None
    return 1e3 * (sum(waves) * 1e-9 - r.trace.busy_s) / len(waves)
