"""Host time per dispatch in finishes with their page frees and in the prefix cache's registration: the part `register` of the `route` phase (`engine_dispatch_part_seconds_total{part}`), window delta over dispatches; a mean hides that it is paid in the few dispatches where lanes finish: the rows' `register` column shows those.

A program without the counter (before PR 39) gives nothing to read."""

from kbench.parts import part_ms

LAYER = "dispatch"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "output_tok_s"


def read(run):
    return part_ms(run, "register")
