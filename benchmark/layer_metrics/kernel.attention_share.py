"""Device time of the Pallas custom-calls (the paged-attention kernels) over device busy time."""

from kbench import xplane_reduce

LAYER = "attention kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "tpot_p50_ms"


def read(run):
    return xplane_reduce.share_of_busy(run["trace"], lambda opcode: opcode == "custom-call")
