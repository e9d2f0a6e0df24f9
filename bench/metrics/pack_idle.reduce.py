"""Host build: percent of the traced window in which chip 0 ran no
operation while the program's ``mwis.reduce.pack`` host span was open
(``distributed.disredu`` packing the SegPlan and uploading the union
problem at each call)."""

from bench import scopes


def read(run):
    s = scopes.of_run(run)
    return None if s is None else scopes.idle_within_pct(s, "mwis.reduce.pack")
