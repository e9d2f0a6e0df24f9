"""Host build: seconds of set-up spent generating the inputs and building
the system's host structures from them (for the graph cells, the
partition; for serving, the request graphs alone, since the service packs
its plans inside the warm-up's calls), on the host clock."""


def read(run):
    return run.counters.get("host_build_s")
