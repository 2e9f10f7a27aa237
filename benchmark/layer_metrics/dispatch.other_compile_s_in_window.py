"""Seconds of XLA compiles inside the window of anything that is NONE of the engine's own programs (`engine_other_compile_seconds_total`, from jax.monitoring's backend-compile event; not divided): a helper jitted on a new shape, an `.at[].set`, `fold_in`: what `dispatch.compiles_in_window` cannot see; a warm run reads 0, and the rows' `other_compile` column says which dispatch it hit.

A program without the counter (before PR 39) gives nothing to read."""

from kbench.parts import window_delta

LAYER = "dispatch"
UNIT = "s"
SOURCE = "program_counter"
MOVES = "itl_p99_ms"


def read(run):
    return window_delta(run, "engine_other_compile_seconds_total")
