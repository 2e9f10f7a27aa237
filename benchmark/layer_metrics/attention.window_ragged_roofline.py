"""The packed step's window attention kernel against the chip's roofline, whichever bound holds.

The least time it could take a second: the larger of operations (`engine_window_ragged_work_total{unit="pairs"}`: (query, key) pairs inside the window, x 4 x query heads x head size, over the chip's bf16 peak) and bytes (`unit="keys"` x K and V of one token + `unit="queries"` x a query in and an output out, over the chip's HBM bytes a second), each summed over the window before the larger is taken; over the kernel's device seconds a second (the trace's `window_attention_ragged*` operations over its window).  A long chunk is bound by operations, a decode lane's one-token slice by the ring it must read.  The counters are the measured window's and the seconds the traced stretch's, as `attention.decode_roofline`.  Returns nothing for another family, without a trace, the kernel or the counter."""

from kbench import cohere_math, loop_math
from kbench.server import metric_delta

LAYER = "attention kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "output_tok_s"

COUNTER = "engine_window_ragged_work_total"


def read(run):
    trace, cfg, peaks = run["trace"], run["hf_config"], run.get("peaks")
    if (not trace or not trace.get("window_s") or not peaks
            or not cohere_math.is_cohere2_moe(cfg)
            or not loop_math.has_series(run["after"], COUNTER)):
        return None
    kernel_s = cohere_math.kernel_seconds(trace)
    work = {unit: metric_delta(run["before"], run["after"], COUNTER, unit=unit)
            for unit in ("pairs", "keys", "queries")}
    if not kernel_s or work["pairs"] <= 0:
        return None
    least_s = max(
        cohere_math.window_ragged_flops(cfg, work["pairs"])
        / peaks["bf16_flops_per_s"],
        cohere_math.window_ragged_bytes(cfg, work["keys"], work["queries"])
        / peaks["hbm_bytes_per_s"])
    device_s_per_s = kernel_s / trace["window_s"]
    return 100.0 * least_s / run["seconds"] / device_s_per_s
