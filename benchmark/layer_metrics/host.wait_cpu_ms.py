"""CPU milliseconds per dispatch of the thread that runs the engine's loop, inside the `wait` phase (`engine_dispatch_phase_cpu_seconds_total{phase="wait"}`), window delta over dispatches: the work the device's step hides (delivery, the SSE writes, the handlers, arriving requests); set it beside `dispatch.wait_ms`.

A program without the counter (before PR 39) gives nothing to read."""

from kbench.parts import phase_cpu_ms

LAYER = "OpenAI surface"
UNIT = "ms"
SOURCE = "program_counter"
MOVES = "output_tok_s"


def read(run):
    return phase_cpu_ms(run, "wait")
