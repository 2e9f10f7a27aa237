"""Device time of the gated short convolutions' own operations, of the packed step and the decode steps alike, over device busy time.

Told by what an operation produces (kbench/conv_math): the input projection's rows of B, C and x together (3 x hidden wide: no other tensor of the model is), the convolution's float32 rows and the lanes' tails.  The output projection's result is [tokens, hidden] in bf16, as a dozen other operations of a layer are, and is NOT in it: the share is a floor of the mixers' time.  Returns nothing for another family's configuration or a trace without such operations."""

from kbench import conv_math

LAYER = "short-convolution layers"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "output_tok_s"


def read(run):
    cfg = run["hf_config"]
    if not conv_math.is_lfm2_moe(cfg):
        return None
    trace = run["trace"]
    if not trace or not trace.get("busy_s"):
        return None
    lanes = run["flags"]["max_batch_size"]
    cap = run["flags"]["max_prefill_len"]
    seconds = conv_math.seconds_of(
        trace, lambda dims, dtype: conv_math.is_in_proj(dims, cfg)
        or conv_math.is_taps(dims, dtype, cfg, lanes, cap))
    return 100.0 * seconds / trace["busy_s"] if seconds else None
