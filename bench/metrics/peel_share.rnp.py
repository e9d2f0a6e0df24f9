"""Round driver: percent of the chip's leaf-op time in the traced solve
that ran under the ``mwis.peel`` scope (the peel score and the peel)."""

from bench import scopes


def read(run):
    s = scopes.of_run(run)
    return None if s is None else scopes.share_pct(s, "mwis.peel")
