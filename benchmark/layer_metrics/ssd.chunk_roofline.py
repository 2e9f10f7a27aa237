"""The packed step's chunked Mamba-2 scan against the chip's roofline, whichever bound holds.

The least time the scans could take a second: the larger of bytes (the window's `engine_ssd_scan_tokens_total`, tokens x Mamba-2 layers, x one token's xBC and dt in and y out; plus, for every packed step and layer, the state and tail of the lanes seated READ once: dispatches x layers x `engine_state_slots_in_use` x one state, nemotron_math.packed_state_pass_bytes; the write is in operations of the state's own shape and counts into `ssd.update_roofline`; over the chip's HBM bytes a second) and operations (tokens x the recurrence's own 4 operations a state element, over the chip's bf16 peak), each summed over the window before the larger is taken; over the scan's device seconds a second (the trace's operations that produce the chunk-end states, decay masks and per-lane windows, kbench/nemotron_math.is_chunk_scan, over its window).  Bytes bind at every length a dispatch packs.  The convolution is not in it (`ssd.share` has it).  Returns nothing for another family, without a trace, the operations or the counters."""

from kbench import loop_math, nemotron_math
from kbench.server import metric_delta, metric_sum

LAYER = "state-space layers"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "output_tok_s"


def read(run):
    trace, cfg, peaks = run["trace"], run["hf_config"], run.get("peaks")
    if (not trace or not trace.get("window_s") or not peaks
            or not nemotron_math.is_nemotron_h(cfg)
            or not loop_math.has_series(
                run["after"], "engine_ssd_scan_tokens_total")):
        return None
    lanes = run["flags"]["max_batch_size"]
    seconds = nemotron_math.seconds_of(
        trace, lambda dims: nemotron_math.is_chunk_scan(dims, cfg, lanes))
    tokens = metric_delta(
        run["before"], run["after"], "engine_ssd_scan_tokens_total")
    dispatches = metric_delta(
        run["before"], run["after"], "engine_dispatches_total")
    seated = min(metric_sum(run["after"], "engine_state_slots_in_use"), lanes)
    if not seconds or tokens <= 0:
        return None
    state = nemotron_math.packed_state_pass_bytes(cfg, dispatches, seated)
    least_s = max(
        (tokens * nemotron_math.scan_bytes_per_token(cfg) + state)
        / peaks["hbm_bytes_per_s"],
        tokens * nemotron_math.scan_flops_per_token(cfg)
        / peaks["bf16_flops_per_s"])
    device_s_per_s = seconds / trace["window_s"]
    return 100.0 * least_s / run["seconds"] / device_s_per_s
