"""Host time per dispatch packing the ragged step (`_plan_ragged` less the sampling state): the part `pack` of the `plan` phase (`engine_dispatch_part_seconds_total{part}`), window delta over dispatches.

A program without the counter (before PR 39) gives nothing to read."""

from kbench.parts import part_ms

LAYER = "dispatch"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "output_tok_s"


def read(run):
    return part_ms(run, "pack")
