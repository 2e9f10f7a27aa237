"""Mean wall time of one engine dispatch (a ragged forward plus steps_per_sync decode steps), from the step-duration histogram's window delta."""

from kbench.server import metric_delta

LAYER = "dispatch"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "tpot_p50_ms"


def read(run):
    before, after = run["before"], run["after"]
    steps = metric_delta(before, after, "engine_decode_step_seconds_count")
    if not steps:
        return None
    return 1e3 * metric_delta(before, after, "engine_decode_step_seconds_sum") / steps
