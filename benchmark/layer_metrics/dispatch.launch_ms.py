"""Host time per dispatch in `launch`: the uploads of the step's arrays and the jitted call up to its return (a compile would land here), window delta."""

from kbench.phases import per_dispatch_ms

LAYER = "dispatch"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "output_tok_s"


def read(run):
    return per_dispatch_ms(run, plus=("launch",))
