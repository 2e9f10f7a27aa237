"""Prompt + generated tokens per engine dispatch, from the window's counter deltas."""

from kbench.server import metric_delta

LAYER = "scheduler"
UNIT = "tokens"
SOURCE = "program_counter"
MOVES = "output_tok_s"


def read(run):
    before, after = run["before"], run["after"]
    steps = metric_delta(before, after, "engine_decode_step_seconds_count")
    if not steps:
        return None
    tokens = (metric_delta(before, after, "engine_prompt_tokens_total")
              + metric_delta(before, after, "engine_generated_tokens_total"))
    return tokens / steps
