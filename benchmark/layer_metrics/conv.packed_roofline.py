"""The packed step's gated convolution (its first product and the taps) against the chip's HBM bandwidth.

The least time it could take a second: the bytes a packed token cannot avoid moving there (the window's `engine_conv_packed_tokens_total`, tokens x short-conv layers, x B and x read and the convolution's row written at 2 B a value: kbench/conv_math.taps_token_bytes; plus the tail of every slice met, `engine_packed_lanes_total`, read and written once a layer) over the chip's HBM bytes a second; over the device seconds a second of the operations that are the convolution's own (conv_math.is_packed_taps: its float32 rows [tokens, hidden] and the tails, over the capture's window).  The second product (C in, y out) is fused by XLA into the output projection: neither its bytes nor the projection's seconds are here.  Returns nothing for another family, without a trace or the counters, and where no operation is the convolution's own: a projection's seconds are never divided by the convolution's bytes."""

from kbench import conv_math, loop_math
from kbench.server import metric_delta

LAYER = "short-convolution layers"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "output_tok_s"


def read(run):
    trace, cfg, peaks = run["trace"], run["hf_config"], run.get("peaks")
    if (not trace or not trace.get("window_s") or not peaks
            or not conv_math.is_lfm2_moe(cfg)
            or not loop_math.has_series(
                run["after"], "engine_conv_packed_tokens_total")):
        return None
    lanes = run["flags"]["max_batch_size"]
    cap = run["flags"]["max_prefill_len"]
    seconds = conv_math.seconds_of(
        trace, lambda dims, dtype: conv_math.is_packed_taps(
            dims, dtype, cfg, lanes, cap))
    tokens = metric_delta(
        run["before"], run["after"], "engine_conv_packed_tokens_total")
    if not seconds or tokens <= 0:
        return None
    slices = metric_delta(
        run["before"], run["after"], "engine_packed_lanes_total")
    least_s = conv_math.packed_bytes(cfg, tokens, slices) / peaks["hbm_bytes_per_s"]
    device_s_per_s = seconds / trace["window_s"]
    return 100.0 * least_s / run["seconds"] / device_s_per_s
