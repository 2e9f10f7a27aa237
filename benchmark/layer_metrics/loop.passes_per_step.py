"""Passes of the layer stack per forward step: `engine_layer_passes_total` (passes run, counted at launch) over the window's forward steps (dispatches x the policy's steps a dispatch).

4.0 for a model of four passes, and the first thing to fall if a program skips one.  A program without the counter gives nothing to read."""

from kbench import loop_math, manifest
from kbench.server import metric_delta

LAYER = "model forward"
UNIT = "count"
SOURCE = "program_counter"
MOVES = "tpot_p50_ms"


def read(run):
    before, after = run["before"], run["after"]
    if not loop_math.has_series(after, "engine_layer_passes_total"):
        return None
    dispatches = metric_delta(before, after, "engine_dispatches_total")
    if dispatches <= 0:
        return None
    policy = manifest.resolve_cell(run["cell"]).deployment["engine_policy"]
    steps = dispatches * policy["tokens_per_dispatch"]
    return metric_delta(before, after, "engine_layer_passes_total") / steps
