"""Device time of the operations named sort* over device busy time.

The sampler sorts the full [lanes, vocab] logits unconditionally."""

from kbench import xplane_reduce

LAYER = "sampler"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "tpot_p50_ms"


def read(run):
    return xplane_reduce.share_of_busy(run["trace"], lambda opcode: opcode == "sort")
