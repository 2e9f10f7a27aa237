"""Host time per dispatch in `fold_in` and the jitted program's call up to its return: the part `call` of the `launch` phase (`engine_dispatch_part_seconds_total{part}`), window delta over dispatches.

A program without the counter (before PR 39) gives nothing to read."""

from kbench.parts import part_ms

LAYER = "dispatch"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "output_tok_s"


def read(run):
    return part_ms(run, "call")
