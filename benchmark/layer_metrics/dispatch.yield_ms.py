"""The event loop's share of a dispatch: `yield` (the engine's `asyncio.sleep(0)`, in which the SSE writes and HTTP handlers run) plus `wait_lag` (a finished result waiting for the loop), window delta."""

from kbench.phases import per_dispatch_ms

LAYER = "OpenAI surface"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "output_tok_s"


def read(run):
    return per_dispatch_ms(run, plus=("yield", "wait_lag"))
