"""Rules: percent of the chip's leaf-op time in the traced solve that ran
under a ``mwis.rule.*`` scope (the cheap rule families and the heavy
vertex)."""

from bench import scopes


def read(run):
    s = scopes.of_run(run)
    return None if s is None else scopes.share_pct(s, "mwis.rule.")
