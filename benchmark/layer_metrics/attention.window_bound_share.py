"""Decode lane-steps whose context was past the window, over all decode lane-steps of the window: `engine_window_lane_steps_total{bound="yes"}` over both labels.

A bound step reads `sliding_window` tokens of each ring whatever the lane's context; a step that is not reads the context.  Between 30 and 70 in a cell whose lanes pass the window while they decode: both kinds in one dispatch.  A program without the counter (no layer keeps a ring; before PR 43) gives nothing to read."""

from kbench import loop_math
from kbench.server import metric_delta

LAYER = "attention kernels"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "tpot_p50_ms"

COUNTER = "engine_window_lane_steps_total"


def read(run):
    before, after = run["before"], run["after"]
    if not loop_math.has_series(after, COUNTER):
        return None
    bound = metric_delta(before, after, COUNTER, bound="yes")
    total = metric_delta(before, after, COUNTER)
    if total <= 0:
        return None
    return 100.0 * bound / total
