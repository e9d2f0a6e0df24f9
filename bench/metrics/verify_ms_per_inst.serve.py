"""Serving: milliseconds per instance of the service's result check in the
traced batch: the self time of its ``mwis.serve.verify`` host spans (the
weight sum and the independence check of each answer) over the instances
of that batch."""

from bench import scopes


def read(run):
    s = scopes.of_run(run)
    t = None if s is None else scopes.self_s(s, "mwis.serve.verify")
    return None if t is None else 1e3 * t / run.cell.traffic["batch"]
