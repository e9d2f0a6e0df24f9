"""Rules: percent of the chips' leaf-op time in the traced window that ran
under the ``mwis.rule.heavy`` scope (the heavy-vertex rule's exact
sub-MWIS), averaged over the chips."""

from bench import scopes


def read(run):
    s = scopes.of_run(run)
    return None if s is None else scopes.share_pct(s, "mwis.rule.heavy")
