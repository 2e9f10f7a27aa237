"""Bytes the window's forward steps had to stream from HBM (each pass the whole layer stack, each step the head once: kbench/loop_math.py) a second, over the chip's HBM bytes a second.

A share of the WHOLE step, bound by bytes: no step can take less than its weights take to stream, whatever the batch, so this cannot pass 100 %; what is missing from 100 is attention, the row writes, the sampler and the host.  Passes and steps from `engine_layer_passes_total`; a program without the counter gives nothing to read."""

from kbench import loop_math
from kbench.server import metric_delta

LAYER = "model forward"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "output_tok_s"


def read(run):
    before, after, cfg = run["before"], run["after"], run["hf_config"]
    if not run.get("peaks") or not loop_math.has_series(
            after, "engine_layer_passes_total"):
        return None
    n_passes = metric_delta(before, after, "engine_layer_passes_total")
    if n_passes <= 0:
        return None
    steps = n_passes / loop_math.passes(cfg)
    streamed = (n_passes * loop_math.stack_bytes(cfg)
                + steps * loop_math.head_bytes(cfg))
    return 100.0 * streamed / run["seconds"] / (
        run["chips"] * run["peaks"]["hbm_bytes_per_s"])
