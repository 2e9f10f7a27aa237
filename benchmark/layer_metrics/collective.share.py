"""Device time of all-reduce / all-gather / reduce-scatter / collective-permute / all-to-all over device busy time."""

from kbench import xplane_reduce

LAYER = "collectives"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "tpot_p50_ms"


def read(run):
    return xplane_reduce.share_of_busy(run["trace"], xplane_reduce.is_collective)
