"""The share of the packed step's lanes that the decode kernel attends for.

A `mixed` dispatch's packed step holds a slice a lane: a prompt chunk, or the ONE token of a lane that is decoding.  Since PR 46 a program built with both kernels hands the one-token lanes to the decode kernel in one call over the lanes (`ops/pallas_paged_attention.ragged_single_token_split_pallas`: eight lanes a block, a page DMA a lane in flight) and leaves the ragged kernel the longer slices; before it the ragged kernel walked every lane's pages alone, a block a lane in series.  100 x decode_kernel / (decode_kernel + ragged) over the window's `engine_packed_lanes_total{attention_path}`, counted on the host at planning from the plan's slice lengths and how the program was built (`dispatch.attention.packed_single_token_min_pages` on `/v1/internal/scheduler/state`).  Near 100 in a saturated decode cell (every seated lane decodes, a prompt chunk or two beside them); 0 where the program does not split: another attention form in the packed step (rings, latent pages), a shape whose decode steps gather, no TPU.

A program without the counter (before PR 46) gives nothing to read."""

from kbench.parts import window_delta

LAYER = "attention kernels"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "tpot_p50_ms"


def read(run):
    decode = window_delta(run, "engine_packed_lanes_total",
                          attention_path="decode_kernel")
    ragged = window_delta(run, "engine_packed_lanes_total",
                          attention_path="ragged")
    if decode is None or ragged is None or decode + ragged <= 0:
        return None
    return 100.0 * decode / (decode + ragged)
