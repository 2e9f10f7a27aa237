"""The Mamba-2 mixers' writes of the lanes' state (the decode step's update, and what the packed step leaves each lane) against the chip's memory bandwidth.

Bytes those operations must move a second: the decode updates' (the window's `engine_ssd_update_lane_steps_total`: live lanes summed over the decode steps and Mamba-2 layers, x what one lane's update cannot avoid moving, kbench/nemotron_math.update_bytes_per_lane: state and tail read and written once, xBC and dt in, y out) plus the packed steps' (dispatches x Mamba-2 layers x `engine_state_slots_in_use` x one state and tail, written once: nemotron_math.packed_state_pass_bytes; the read is `ssd.chunk_roofline`'s) / the operations' device seconds a second (the trace's operations that produce the state [lanes, heads, head_dim, state] or the convolution's window or tail, over its window: BOTH steps', which a label cannot tell apart, so both steps' bytes are counted) / the chip's HBM bytes a second.  Bound by bytes: 4 operations a state element against 8 bytes.  The decode update alone is the operation `fusion_f32_<lanes>_64_64_128_` in `breakdown.device_ops`.  The counters are the measured window's and the seconds the traced stretch's.  Returns nothing for another family, without a trace, the operations or the counter."""

from kbench import loop_math, nemotron_math
from kbench.server import metric_delta, metric_sum

LAYER = "state-space layers"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "tpot_p50_ms"


def read(run):
    trace, cfg, peaks = run["trace"], run["hf_config"], run.get("peaks")
    if (not trace or not trace.get("window_s") or not peaks
            or not nemotron_math.is_nemotron_h(cfg)
            or not loop_math.has_series(
                run["after"], "engine_ssd_update_lane_steps_total")):
        return None
    lanes = run["flags"]["max_batch_size"]
    seconds = nemotron_math.seconds_of(
        trace, lambda dims: nemotron_math.is_update(dims, cfg, lanes))
    lane_steps = metric_delta(
        run["before"], run["after"], "engine_ssd_update_lane_steps_total")
    if not seconds or lane_steps <= 0:
        return None
    dispatches = metric_delta(
        run["before"], run["after"], "engine_dispatches_total")
    seated = min(metric_sum(run["after"], "engine_state_slots_in_use"), lanes)
    must_move_per_s = (
        lane_steps * nemotron_math.update_bytes_per_lane(cfg)
        + nemotron_math.packed_state_pass_bytes(cfg, dispatches, seated)
    ) / run["seconds"]
    device_s_per_s = seconds / trace["window_s"]
    return 100.0 * must_move_per_s / device_s_per_s / peaks["hbm_bytes_per_s"]
