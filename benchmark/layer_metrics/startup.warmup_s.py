"""The benchmark's shape warm-up (the grid of program shapes) on the parent's clock: tracing plus compile or cache load of every shape the cell can reach."""

LAYER = "start-up"
UNIT = "s"
SOURCE = "host_clock"
MOVES = "setup_s"


def read(run):
    return run["timings"]["grid_s"]
