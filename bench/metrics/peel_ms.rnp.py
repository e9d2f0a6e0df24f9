"""Round driver: milliseconds of the window's reduce-and-peel solves (the
host spans around each call, which leave out starting and stopping a
trace) per peel, over the peels that those solves returned."""


def read(run):
    peels = run.counters.get("peels")
    calls = sum(t1 - t0 for name, t0, t1 in run.spans.spans if name == "call")
    return 1e3 * calls / peels if peels else None
