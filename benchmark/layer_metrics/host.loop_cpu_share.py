"""CPU seconds of the thread that runs the engine's loop, all six phases (`engine_dispatch_phase_cpu_seconds_total`), over the wall seconds of the same phases (`engine_dispatch_phase_seconds_total`), window delta: how busy the one thread is that plans, launches, delivers and serves; near 100 the host is the bottleneck.

A program without the counter (before PR 39) gives nothing to read."""

from kbench.parts import loop_cpu_share

LAYER = "OpenAI surface"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "output_tok_s"


def read(run):
    return loop_cpu_share(run)
