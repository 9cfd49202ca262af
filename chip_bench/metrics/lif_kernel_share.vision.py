"""Share (%) of the LIF neurons' device time that runs in one pass over
T_s: device time under ``lif.kernel`` over device time under ``lif.``
(every LIF call, the scan's included). A program without the one-pass
scope reads nothing."""


def read(r):
    lif = r.trace.scope_s("lif.")
    one_pass = r.trace.scope_s("lif.kernel")
    if not lif or not one_pass:
        return None
    return 100.0 * one_pass / lif
