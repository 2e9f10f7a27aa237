"""The model's FLOPs utilisation over the whole step: served tokens (prompt + output) per second x 2 x the parameters a token multiplies, over chips x the chip's bf16 peak.

The share of the chips' arithmetic that the served tokens needed, whatever kernels did the work: it still reads where a kernel is taken off the path and that kernel's own roofline falls silent.  Not a kernel's roofline share."""

from kbench.model_math import forward_flops_per_token
from kbench.server import metric_delta

LAYER = "model forward"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "output_tok_s"


def read(run):
    if not run.get("peaks"):
        return None
    before, after = run["before"], run["after"]
    tokens = (metric_delta(before, after, "engine_prompt_tokens_total")
              + metric_delta(before, after, "engine_generated_tokens_total"))
    flops = forward_flops_per_token(run["hf_config"]) * tokens / run["seconds"]
    return 100.0 * flops / (run["chips"] * run["peaks"]["bf16_flops_per_s"])
