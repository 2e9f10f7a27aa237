"""Host time per dispatch rebuilding the sampling state (`SamplingState.planned` in `_prepare_chunk` and in `_plan_ragged`): the part `sampling` of the `plan` phase (`engine_dispatch_part_seconds_total{part}`), window delta over dispatches.

A program without the counter (before PR 39) gives nothing to read."""

from kbench.parts import part_ms

LAYER = "dispatch"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "output_tok_s"


def read(run):
    return part_ms(run, "sampling")
