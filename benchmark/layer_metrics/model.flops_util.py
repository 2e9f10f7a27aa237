"""End-to-end utilisation: 2 x matmul parameters x (prompt + output tokens per second) over chips x the chip's bf16 peak.

Not a roofline share and not an mfu of a kernel: it says how much of the chips' arithmetic the served tokens needed."""

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
