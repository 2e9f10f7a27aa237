"""Jit-cache misses inside the window (engine_xla_compiles_total); anything but 0 makes the run not correct."""

from kbench.server import metric_delta

LAYER = "dispatch"
UNIT = "count"
SOURCE = "program_counter"
MOVES = "tpot_p50_ms"


def read(run):
    return metric_delta(run["before"], run["after"], "engine_xla_compiles_total")
