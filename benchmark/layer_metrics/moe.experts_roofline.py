"""The routed experts' grouped matmuls against the chip's roofline, whichever bound holds.

The least time they could take a second: the larger of bytes (`engine_moe_expert_hits_total`: experts that got at least one token, summed over forward steps and expert layers, x one expert's weights, over the chip's HBM bytes a second) and operations (`engine_moe_assignments_total`: (token, expert) pairs, x one pair's operations, over the chip's bf16 peak), both from the window's counters; over the grouped matmuls' device seconds a second (the trace's `ragged-dot*` operations over its window).  Bytes bind a decode step (3 rows an expert), operations would bind a packed step past ~240 rows an expert.  Hits are counted, not assumed to be every expert, and each bound is summed over the window before the larger is taken, which is never more than the sum of each step's larger: the share cannot read high for experts no token reached.  The counters are the measured window's and the seconds the traced stretch's (as `attention.latent_decode_roofline`).  Returns nothing without a trace, a grouped matmul or the counters."""

from kbench import expert_math, loop_math
from kbench.server import metric_delta

LAYER = "expert layers"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "output_tok_s"


def read(run):
    trace, cfg, peaks = run["trace"], run["hf_config"], run.get("peaks")
    if (not trace or not trace.get("window_s") or not peaks
            or not expert_math.has_experts(cfg)
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
        hits * expert_math.expert_bytes(cfg) / peaks["hbm_bytes_per_s"],
        pairs * expert_math.pair_flops(cfg) / peaks["bf16_flops_per_s"])
    device_s_per_s = matmul_s / trace["window_s"]
    return 100.0 * least_s / run["seconds"] / device_s_per_s
