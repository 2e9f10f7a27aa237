"""Share of the dispatch period that is not the device's: everything but `wait` less `wait_lag`, over the six phases' sum; the program's own reading of idle, from the measured window; set it beside `device.idle_share`."""

from kbench.phases import window_seconds

LAYER = "dispatch"
UNIT = "%"
SOURCE = "program_span"
MOVES = "output_tok_s"


def read(run):
    w = window_seconds(run)
    if w is None or not w["all"]:
        return None
    return 100.0 * (1.0 - (w["wait"] - w["wait_lag"]) / w["all"])
