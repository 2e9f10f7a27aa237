"""The packed step's chunked delta rule against the chip's roofline, whichever bound holds.

The least time it could take a second: the larger of bytes (the window's `engine_kda_chunk_tokens_total`, tokens x Kimi-delta layers, x one token's q, k, v, decay and step size in and output out, kbench/delta_math.token_bytes; plus, for every packed step and layer, the state and tail of the lanes seated READ once: delta_math.packed_state_pass_bytes; the write is in operations of the state's own shape and counts into `kda.update_roofline`; over the chip's HBM bytes a second) and operations (tokens x the three contractions with the state that every form of the recurrence has, 6 operations a state element, over the chip's bf16 peak), each summed over the window before the larger is taken; over the chunked form's device seconds a second (the trace's operations that produce one piece's arrays and states, delta_math.is_chunk, over its window).  Bytes bind at every length a dispatch packs.  The convolution is not in it (`kda.share` has it).  Returns nothing for another family, without a trace, the operations or the counters."""

from kbench import delta_math, loop_math
from kbench.server import metric_delta, metric_sum

LAYER = "linear-attention layers"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "output_tok_s"


def read(run):
    trace, cfg, peaks = run["trace"], run["hf_config"], run.get("peaks")
    if (not trace or not trace.get("window_s") or not peaks
            or not delta_math.is_solar_open2(cfg)
            or not loop_math.has_series(
                run["after"], "engine_kda_chunk_tokens_total")):
        return None
    lanes = run["flags"]["max_batch_size"]
    seconds = delta_math.seconds_of(
        trace, lambda dims, dtype: delta_math.is_chunk(dims, cfg, lanes, dtype))
    tokens = metric_delta(
        run["before"], run["after"], "engine_kda_chunk_tokens_total")
    if not seconds or tokens <= 0:
        return None
    dispatches = metric_delta(
        run["before"], run["after"], "engine_dispatches_total")
    seated = min(metric_sum(run["after"], "engine_state_slots_in_use"), lanes)
    state = delta_math.packed_state_pass_bytes(cfg, dispatches, seated)
    least_s = max(
        (tokens * delta_math.token_bytes(cfg) + state)
        / peaks["hbm_bytes_per_s"],
        tokens * delta_math.chunk_flops_per_token(cfg)
        / peaks["bf16_flops_per_s"])
    device_s_per_s = seconds / trace["window_s"]
    return 100.0 * least_s / run["seconds"] / device_s_per_s
