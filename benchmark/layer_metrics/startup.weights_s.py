"""Sum of engine_startup_seconds{phase=weights} up to the end of the warm-up (reading or making the weights and placing them on the device); nothing where the program records none."""

from kbench.server import metric_sum

LAYER = "start-up"
UNIT = "s"
SOURCE = "program_span"
MOVES = "setup_s"


def read(run):
    n = metric_sum(run["startup_metrics"], "engine_startup_seconds_count", phase="weights")
    if not n:
        return None
    return metric_sum(run["startup_metrics"], "engine_startup_seconds_sum", phase="weights")
