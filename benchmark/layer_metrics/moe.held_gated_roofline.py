"""The held experts' grouped matmuls against the chip's roofline, whichever bound holds: `moe.held_experts_roofline` for GATED experts whose width is the family's `intermediate_size`.

The least time they could take a second: the larger of bytes (`engine_moe_expert_hits_total`: HELD experts that got at least one token, summed over forward steps and expert layers, x one expert's three matrices, 3 x hidden x width x 2 B, over the chip's HBM bytes a second) and operations (`engine_moe_assignments_total`: the pairs THIS chip multiplied, x 6 x hidden x width, over the chip's bf16 peak), each summed over the window before the larger is taken; over the grouped matmuls' device seconds a second (the trace's `ragged-dot*` operations over its window).  Returns nothing for another family (`kbench/expert_math.has_experts` reads other keys), without a trace, a grouped matmul or the counters."""

from kbench import cohere_math, expert_math, loop_math
from kbench.server import metric_delta

LAYER = "expert layers"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "output_tok_s"


def read(run):
    trace, cfg, peaks = run["trace"], run["hf_config"], run.get("peaks")
    if (not trace or not trace.get("window_s") or not peaks
            or not cohere_math.is_cohere2_moe(cfg)
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
        hits * cohere_math.held_expert_bytes(cfg) / peaks["hbm_bytes_per_s"],
        pairs * cohere_math.held_pair_flops(cfg) / peaks["bf16_flops_per_s"])
    device_s_per_s = matmul_s / trace["window_s"]
    return 100.0 * least_s / run["seconds"] / device_s_per_s
