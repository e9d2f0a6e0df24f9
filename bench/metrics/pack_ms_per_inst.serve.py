"""Serving: milliseconds per instance of the service's host packing in the
traced batch: the self time of its ``mwis.serve.pack`` host spans (request
packing and stacking, less the nested reduce packing of a new topology)
over the instances of that batch."""

from bench import scopes


def read(run):
    s = scopes.of_run(run)
    t = None if s is None else scopes.self_s(s, "mwis.serve.pack")
    return None if t is None else 1e3 * t / run.cell.traffic["batch"]
