"""Host time per dispatch handing deferred tokens to their streams (detokenise, the output, the queue put): the part `deliver` (`engine_dispatch_part_seconds_total{part}`), inside `wait` behind the next launch on `mixed`, window delta over dispatches; it costs CPU and no latency while it fits under the device's step.

A program without the counter (before PR 39) gives nothing to read."""

from kbench.parts import part_ms

LAYER = "dispatch"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "tpot_p50_ms"


def read(run):
    return part_ms(run, "deliver")
