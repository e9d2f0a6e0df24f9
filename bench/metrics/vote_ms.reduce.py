"""Round driver: milliseconds of device time per reduce call under the
``mwis.round.vote`` scope (the round's changed test and, across chips, its
``psum``), averaged over the chips.  The PEs reach the vote in step: the
wait for the slowest PE falls earlier, in the exchange's collectives
(``exchange_share.reduce``)."""

from bench import scopes


def read(run):
    s = scopes.of_run(run)
    if s is None or not s["calls"]:
        return None
    t = scopes.under_s(s, "mwis.round.vote")
    return 1e3 * t / s["calls"] if t > 0 else None
