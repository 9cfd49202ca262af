"""Share of the traced window in which no op ran on the device."""


def read(r):
    return 100.0 * r.trace.idle_share()
