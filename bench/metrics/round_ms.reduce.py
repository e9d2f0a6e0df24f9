"""Round driver: milliseconds of the window's reduce calls (the host spans
around each call, which leave out starting and stopping a trace) per
DisReduA round, over the rounds that those calls returned."""


def read(run):
    rounds = run.counters.get("rounds")
    calls = sum(t1 - t0 for name, t0, t1 in run.spans.spans if name == "call")
    return 1e3 * calls / rounds if rounds else None
