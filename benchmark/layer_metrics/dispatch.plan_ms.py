"""Host time per dispatch before the launch: admission (`admit`) and packing the step (`plan`: `_prepare_chunk` + `_plan_ragged`), from the engine's phase counters, window delta."""

from kbench.phases import per_dispatch_ms

LAYER = "dispatch"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "output_tok_s"


def read(run):
    return per_dispatch_ms(run, plus=("admit", "plan"))
