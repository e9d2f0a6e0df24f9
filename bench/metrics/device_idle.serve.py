"""Device: percent of the traced window in which no operation ran on the
chips (1 minus the busy union over the window, averaged over chips)."""

from bench import trace


def read(run):
    return trace.idle_pct(run.summary) if run.summary else None
