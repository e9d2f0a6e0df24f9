"""Aggregate: percent of the chips' leaf-op time in the traced window that
ran under the ``mwis.aggregate`` scope (``engine.compute_ctx``: the payload
gathers and the segment aggregate, the Pallas kernel included), averaged
over the chips."""

from bench import scopes


def read(run):
    s = scopes.of_run(run)
    return None if s is None else scopes.share_pct(s, "mwis.aggregate")
