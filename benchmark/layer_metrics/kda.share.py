"""Device time of the Kimi-delta mixers' convolution, chunked form and update operations, of the packed step and the decode steps alike, over device busy time.

Told by what an operation produces (kbench/delta_math.is_kda): the matrix-valued state, arrays over the convolution's columns (q, k and v together: the projection that makes them among them), the float32 arrays a token or lane and head of the recurrence's inputs and output, one piece's decayed scores, triangular system and states.  The other projections and the gated norm's product with the gate are dense operations over [tokens, hidden or heads x head_dim] and are not in it.  Returns nothing for another family's configuration or a trace without such operations."""

from kbench import delta_math

LAYER = "linear-attention layers"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "tpot_p50_ms"


def read(run):
    cfg = run["hf_config"]
    if not delta_math.is_solar_open2(cfg):
        return None
    trace = run["trace"]
    if not trace or not trace.get("busy_s"):
        return None
    lanes = run["flags"]["max_batch_size"]
    seconds = delta_math.seconds_of(
        trace, lambda dims, dtype: delta_math.is_kda(dims, cfg, lanes, dtype))
    return 100.0 * seconds / trace["busy_s"] if seconds else None
