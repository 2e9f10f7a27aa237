"""Device time of the Mamba-2 mixers' convolution, scan and update operations, of the packed step and the decode steps alike, over device busy time.

Told by what an operation produces (kbench/nemotron_math.is_ssd): the matrix-valued state, the convolution's window or tail over x, B and C together, the chunked scan's decay masks, chunk-end states and per-lane windows.  The projections around them (`in_proj`, `out_proj`) and the gated norm are dense operations of the layer and are not in it.  Returns nothing for another family's configuration or a trace without such operations."""

from kbench import nemotron_math, state_math

LAYER = "state-space layers"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "tpot_p50_ms"


def read(run):
    cfg = run["hf_config"]
    if not nemotron_math.is_nemotron_h(cfg):
        return None
    lanes = run["flags"]["max_batch_size"]
    return state_math.share_of_labels(
        run["trace"], lambda label: nemotron_math.is_ssd(
            state_math.label_dims(label) or [], cfg, lanes))
