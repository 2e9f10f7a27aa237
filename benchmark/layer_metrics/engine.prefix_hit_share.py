"""Prompt tokens served from cached prefix pages over all prompt tokens admitted in the window.

The control in an unshared mix: about 0."""

from kbench.server import metric_delta

LAYER = "scheduler"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "ttft_mean_ms"


def read(run):
    before, after = run["before"], run["after"]
    hit = metric_delta(before, after, "kv_prefix_hit_tokens_total")
    prefilled = metric_delta(before, after, "engine_prompt_tokens_total")
    if hit + prefilled <= 0:
        return None
    return 100.0 * hit / (hit + prefilled)
