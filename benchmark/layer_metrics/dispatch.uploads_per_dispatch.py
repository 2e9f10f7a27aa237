"""Host-to-device transfers of a dispatch's inputs: the window's delta of `engine_dispatch_uploads_total` over the delta of `engine_dispatches_total`.

Every transfer of an input the engine's loop built for a launch goes through one helper (`LLMEngine._upload`), which counts it; a transfer costs the loop about a quarter of a millisecond whatever it carries, so a `mixed` dispatch hands the device its inputs in three packed buffers (`engine/shapes.MixedLayout`: the tokens' buffer, the lanes' buffer, the page table) and this reads 3.  More than 3 means that someone gave a launch one more argument of its own.

A program without the counter (before PR 45, where a dispatch made thirty-one transfers and one eager `fold_in`) gives nothing to read."""

from kbench.parts import window_delta
from kbench.server import metric_delta

LAYER = "dispatch"
UNIT = "count"
SOURCE = "program_counter"
MOVES = "output_tok_s"


def read(run):
    uploads = window_delta(run, "engine_dispatch_uploads_total")
    dispatches = metric_delta(
        run["before"], run["after"], "engine_dispatches_total")
    if uploads is None or not dispatches:
        return None
    return uploads / dispatches
