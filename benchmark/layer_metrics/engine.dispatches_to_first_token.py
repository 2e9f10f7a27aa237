"""Mean over the window's first tokens of (first-token dispatch - admitting dispatch + 1), from `engine_first_token_dispatches`: TTFT = queue wait + this many dispatch periods."""

from kbench.server import metric_delta

LAYER = "scheduler"
UNIT = "count"
SOURCE = "program_span"
MOVES = "ttft_mean_ms"


def read(run):
    before, after = run["before"], run["after"]
    n = metric_delta(before, after, "engine_first_token_dispatches_count")
    if not n:
        return None
    return metric_delta(before, after, "engine_first_token_dispatches_sum") / n
