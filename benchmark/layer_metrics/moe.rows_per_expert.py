"""Rows an expert multiplies when it is reached: `engine_moe_assignments_total` ((token, expert) pairs) over `engine_moe_expert_hits_total` (experts that got at least one token, summed over forward steps and expert layers), over the window.

About 3 in a decode step of 48 lanes, about 100 in a packed step of 2048 tokens; the mean over a window's steps lies between, weighted by hits.  The fullest expert's rows are counted beside them (`engine_moe_peak_load_total`, a sum of one maximum a step and layer): over the mean rows of ALL experts a step and layer that is the imbalance, 1.0 where routing is even; the harness's `detail` has no place for it (PERF.md, open questions).  A program without the counters gives nothing to read."""

from kbench import loop_math
from kbench.server import metric_delta

LAYER = "expert layers"
UNIT = "count"
SOURCE = "program_counter"
MOVES = "output_tok_s"


def read(run):
    before, after = run["before"], run["after"]
    if not loop_math.has_series(after, "engine_moe_expert_hits_total"):
        return None
    hits = metric_delta(before, after, "engine_moe_expert_hits_total")
    if hits <= 0:
        return None
    return metric_delta(before, after, "engine_moe_assignments_total") / hits

