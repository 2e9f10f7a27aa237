"""The held experts' grouped matmuls against the chip's roofline, whichever bound holds: `moe.experts_roofline` for ungated experts of which this chip holds a share.

The least time they could take a second: the larger of bytes (`engine_moe_expert_hits_total`: HELD experts that got at least one token, summed over forward steps and expert layers, x one expert's two matrices, 2 x hidden x width x 2 B, over the chip's HBM bytes a second) and operations (`engine_moe_assignments_total`: the pairs THIS chip multiplied, x 4 x hidden x width, over the chip's bf16 peak), each summed over the window before the larger is taken; over the grouped matmuls' device seconds a second (the trace's `ragged-dot*` operations over its window).  The width is the model's 1856, not the 2048 columns it is stored in: bytes that must move.  Returns nothing for another family, without a trace, a grouped matmul or the counters."""

from kbench import expert_math, loop_math, nemotron_math
from kbench.server import metric_delta

LAYER = "expert layers"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "output_tok_s"


def read(run):
    trace, cfg, peaks = run["trace"], run["hf_config"], run.get("peaks")
    if (not trace or not trace.get("window_s") or not peaks
            or not nemotron_math.is_nemotron_h(cfg)
            or not loop_math.has_series(
                run["after"], "engine_moe_expert_hits_total")):
        return None
    matmul_s = expert_math.grouped_matmul_seconds(trace)
    hits = metric_delta(
        run["before"], run["after"], "engine_moe_expert_hits_total")
    pairs = metric_delta(
        run["before"], run["after"], "engine_moe_assignments_total")
    if not matmul_s or hits <= 0:
        return None
    least_s = max(
        hits * nemotron_math.held_expert_bytes(cfg) / peaks["hbm_bytes_per_s"],
        pairs * nemotron_math.held_pair_flops(cfg) / peaks["bf16_flops_per_s"])
    device_s_per_s = matmul_s / trace["window_s"]
    return 100.0 * least_s / run["seconds"] / device_s_per_s
