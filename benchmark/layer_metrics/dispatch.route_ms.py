"""Host time per dispatch in `route`: `_route_mixed` (detokenise, stop conditions, queue puts), window delta."""

from kbench.phases import per_dispatch_ms

LAYER = "dispatch"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "output_tok_s"


def read(run):
    return per_dispatch_ms(run, plus=("route",))
