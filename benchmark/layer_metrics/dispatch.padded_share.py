"""Mixed dispatches that ran padded in a loaded (T, W) pair, because the pair they needed was not loaded, over all mixed dispatches of the window (`engine_dispatch_shape_total{fit}`: exact | padded | compiled).

A program without the counter (before PR 33) gives nothing to read."""

from kbench.server import metric_delta

LAYER = "dispatch"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "tpot_p50_ms"


def read(run):
    before, after = run["before"], run["after"]
    padded = metric_delta(before, after, "engine_dispatch_shape_total", fit="padded")
    total = metric_delta(before, after, "engine_dispatch_shape_total")
    if total <= 0:
        return None
    return 100.0 * padded / total
