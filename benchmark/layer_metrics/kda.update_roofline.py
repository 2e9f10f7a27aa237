"""The Kimi-delta mixers' writes of the lanes' state (the decode step's update, and what the packed step leaves each lane) against the chip's memory bandwidth.

Bytes those operations must move a second: the decode updates' (the window's `engine_kda_update_lane_steps_total`: live lanes summed over the decode steps and Kimi-delta layers, x what one lane's update cannot avoid moving, kbench/delta_math.update_bytes_per_lane: state and tails read and written once, q, k, v, the decay and the step size in, the output out) plus the packed steps' (dispatches x layers x `engine_state_slots_in_use` x one state and tail, written once: delta_math.packed_state_pass_bytes; the read is `kda.chunk_roofline`'s) / the operations' device seconds a second (the trace's operations that produce the state [lanes, heads, head_dim, head_dim] or the convolution's window or tail, over its window: BOTH steps', which a label cannot tell apart, so both steps' bytes are counted) / the chip's HBM bytes a second.  Bound by bytes: 7 operations a state element against 8 bytes.  The counters are the measured window's and the seconds the traced stretch's.  Returns nothing for another family, without a trace, the operations or the counter."""

from kbench import delta_math, loop_math
from kbench.server import metric_delta, metric_sum

LAYER = "linear-attention layers"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "tpot_p50_ms"


def read(run):
    trace, cfg, peaks = run["trace"], run["hf_config"], run.get("peaks")
    if (not trace or not trace.get("window_s") or not peaks
            or not delta_math.is_solar_open2(cfg)
            or not loop_math.has_series(
                run["after"], "engine_kda_update_lane_steps_total")):
        return None
    lanes = run["flags"]["max_batch_size"]
    seconds = delta_math.seconds_of(
        trace, lambda dims, dtype: delta_math.is_update(dims, cfg, lanes, dtype))
    lane_steps = metric_delta(
        run["before"], run["after"], "engine_kda_update_lane_steps_total")
    if not seconds or lane_steps <= 0:
        return None
    dispatches = metric_delta(
        run["before"], run["after"], "engine_dispatches_total")
    seated = min(metric_sum(run["after"], "engine_state_slots_in_use"), lanes)
    must_move_per_s = (
        lane_steps * delta_math.update_bytes_per_lane(cfg)
        + delta_math.packed_state_pass_bytes(cfg, dispatches, seated)
    ) / run["seconds"]
    device_s_per_s = seconds / trace["window_s"]
    return 100.0 * must_move_per_s / device_s_per_s / peaks["hbm_bytes_per_s"]
