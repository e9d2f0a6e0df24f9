"""Rules: percent of the chips' leaf-op time in the traced window that ran
under a ``mwis.rule.*`` scope (the cheap rule families and the heavy
vertex), averaged over the chips."""

from bench import scopes


def read(run):
    s = scopes.of_run(run)
    return None if s is None else scopes.share_pct(s, "mwis.rule.")
