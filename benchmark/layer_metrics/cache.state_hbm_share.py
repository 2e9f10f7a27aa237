"""Bytes of per-lane state in use at the window's close over the chip's HBM.

The sum of the program's `engine_state_bytes{kind}` gauges (shared_kv:
pages of the pool held; window_kv: the window layers' rings; ssm and conv:
the recurrent layers' slots, for the lanes seated).  Returns nothing where
the program has no such gauge."""

from kbench.server import metric_sum

LAYER = "cache manager"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "output_tok_s"


def read(run):
    if not run.get("peaks") or not any(
            name == "engine_state_bytes" for name, _ in run["after"]):
        return None
    held = metric_sum(run["after"], "engine_state_bytes")
    return 100.0 * held / (run["chips"] * run["peaks"]["hbm_bytes"])
