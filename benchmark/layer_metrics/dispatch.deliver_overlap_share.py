"""Tokens handed to their streams AFTER the next dispatch was launched, while the device ran it, over all tokens handed to their streams, window delta (`engine_dispatch_deliveries_total{when}`: overlapped | inline).

100 where no request carries a stop string and the loop always has a next dispatch to launch; a token handed over inline (a lane with stop strings, the dense and legacy paths, nothing left to launch) sits between the fetch and the next launch, where `dispatch.route_ms` and `dispatch.yield_ms` show it.  A program without the counter (before PR 36) gives nothing to read."""

from kbench.server import metric_delta

LAYER = "dispatch"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "tpot_p50_ms"


def read(run):
    before, after = run["before"], run["after"]
    overlapped = metric_delta(before, after, "engine_dispatch_deliveries_total", when="overlapped")
    total = metric_delta(before, after, "engine_dispatch_deliveries_total")
    if total <= 0:
        return None
    return 100.0 * overlapped / total
