"""Exchange: percent of the chips' leaf-op time in the traced window that
ran under the ``mwis.exchange`` scope (boards, collectives, reconcile),
averaged over the chips.  Across chips this holds the collectives' wait
for the slowest PE."""

from bench import scopes


def read(run):
    s = scopes.of_run(run)
    return None if s is None else scopes.share_pct(s, "mwis.exchange")
