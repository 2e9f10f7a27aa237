"""The device's side of the period: per dispatch, `wait` (awaiting the fetch) less `wait_lag` (result on the host, the loop not yet resumed), window delta."""

from kbench.phases import per_dispatch_ms

LAYER = "dispatch"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "tpot_p50_ms"


def read(run):
    return per_dispatch_ms(run, plus=("wait",), minus=("wait_lag",))
