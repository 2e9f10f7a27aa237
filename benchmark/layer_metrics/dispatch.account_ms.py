"""Host time per dispatch the launch's own counting costs (the row's note, the loaded pairs, the fit and sampler-path counters, `_count_forward`): the part `account` of the `launch` phase (`engine_dispatch_part_seconds_total{part}`), window delta over dispatches.

A program without the counter (before PR 39) gives nothing to read."""

from kbench.parts import part_ms

LAYER = "dispatch"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "output_tok_s"


def read(run):
    return part_ms(run, "account")
