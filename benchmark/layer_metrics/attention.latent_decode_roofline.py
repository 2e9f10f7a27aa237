"""The latent decode kernel against the chip's memory bandwidth.

Latent bytes decode attention had to read a second (the window's `engine_kv_context_tokens_total`: the sum over decode steps of the live lanes' cached tokens, x one token's row as stored x the layers, kbench/latent_math.py) / the kernel's device seconds a second (the trace's `latent_attention_decode*` operations over its window) / the chip's HBM bytes a second.  Bound by bytes: a row is read once for scores and values.  The counter is the measured window's and the seconds are the traced stretch's, of the same traffic under the next seed: they differ by what the two stretches' dispatch periods differ (`detail.timings.traced_phase.period_under_capture_ms` against the window's).  Returns nothing without a trace, the kernel or the counter."""

from kbench import latent_math, loop_math
from kbench.server import metric_delta

LAYER = "attention kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "tpot_p50_ms"


def read(run):
    trace, cfg, peaks = run["trace"], run["hf_config"], run.get("peaks")
    if (not trace or not trace.get("window_s") or not peaks
            or not latent_math.is_latent(cfg)
            or not loop_math.has_series(
                run["after"], "engine_kv_context_tokens_total")):
        return None
    kernel_s = latent_math.kernel_seconds(trace)
    context = metric_delta(
        run["before"], run["after"], "engine_kv_context_tokens_total")
    if not kernel_s or context <= 0:
        return None
    must_read_per_s = latent_math.context_read_bytes(cfg, context) / run["seconds"]
    device_s_per_s = kernel_s / trace["window_s"]
    return 100.0 * must_read_per_s / device_s_per_s / peaks["hbm_bytes_per_s"]
