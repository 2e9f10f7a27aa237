"""Server launch to ready on the parent's clock: process start, weights and cache on the device, the engine's own start."""

LAYER = "start-up"
UNIT = "s"
SOURCE = "host_clock"
MOVES = "setup_s"


def read(run):
    return run["timings"]["ready_s"]
