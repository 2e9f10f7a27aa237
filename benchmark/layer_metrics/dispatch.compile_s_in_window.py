"""Seconds of calls that missed the jit cache inside the window (`engine_xla_compile_seconds_total` delta): how long compiles kept the loop from serving; a warm run reads 0."""

from kbench.server import metric_delta

LAYER = "dispatch"
UNIT = "s"
SOURCE = "program_counter"
MOVES = "ttft_mean_ms"


def read(run):
    return metric_delta(
        run["before"], run["after"], "engine_xla_compile_seconds_total")
