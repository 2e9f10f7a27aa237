"""Sum of engine_startup_seconds{phase=aot_load} up to the end of the warm-up (deserialising the AOT cache's executables); nothing where the program records none."""

from kbench.server import metric_sum

LAYER = "start-up"
UNIT = "s"
SOURCE = "program_span"
MOVES = "setup_s"


def read(run):
    n = metric_sum(run["startup_metrics"], "engine_startup_seconds_count", phase="aot_load")
    if not n:
        return None
    return metric_sum(run["startup_metrics"], "engine_startup_seconds_sum", phase="aot_load")
