"""Milliseconds per dispatch the Python collector held the process (`engine_gc_pause_seconds_total`, generations 1 and 2; generation 0 is not timed), window delta over dispatches: a pause stops the loop's thread wherever it is, so it lengthens a single gap between tokens; the rows' `gc` column says which dispatch it hit.

A program without the counter (before PR 39) gives nothing to read."""

from kbench.parts import per_dispatch_ms

LAYER = "dispatch"
UNIT = "ms"
SOURCE = "program_counter"
MOVES = "itl_p99_ms"


def read(run):
    return per_dispatch_ms(run, "engine_gc_pause_seconds_total")
