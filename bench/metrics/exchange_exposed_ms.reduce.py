"""Exchange: milliseconds per reduce call in which a collective ran on a
chip and no other operation did, averaged over the chips, from the trace
of the window's first call."""


def read(run):
    s = run.summary
    if not s or not s["calls"] or not s["collective_s"]:
        return None
    return 1e3 * s["exposed_s"] / s["calls"]
