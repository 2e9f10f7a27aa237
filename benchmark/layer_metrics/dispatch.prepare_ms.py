"""Host time per dispatch in all of `plan` that precedes the packing: the legacy gate, `_prepare_chunk` less the sampling state (page growth, preemption, the lanes' arrays), the occupancy gauges; the part `prepare` of the `plan` phase (`engine_dispatch_part_seconds_total{part}`), window delta over dispatches.

A program without the counter (before PR 39) gives nothing to read."""

from kbench.parts import part_ms

LAYER = "dispatch"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "output_tok_s"


def read(run):
    return part_ms(run, "prepare")
