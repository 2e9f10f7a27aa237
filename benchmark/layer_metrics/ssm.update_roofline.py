"""The decode step's state update (scan and convolution of one Mamba layer) against the chip's memory bandwidth.

bytes the update must move per call (kbench/state_math.update_bytes_per_call:
state read and written once, inputs and output once, for the lanes seated)
x calls per second / the operations' device seconds per second / the
chip's HBM bytes per second.  Calls: the Mamba layers x the decode steps
of a dispatch (steps_per_sync less the packed step, whose operations have
other shapes and are not counted) x dispatches, from the window's counters;
device seconds from the trace, per second of its window.  Bound by bytes,
not by operations.  Returns nothing for a configuration without Mamba
sizes or a program without the state gauges."""

from kbench import manifest, state_math
from kbench.server import metric_delta, metric_sum

LAYER = "state-space layers"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "tpot_p50_ms"


def read(run):
    trace, cfg, peaks = run["trace"], run["hf_config"], run.get("peaks")
    if (not trace or not trace.get("window_s") or not peaks
            or "mamba_d_inner" not in cfg):
        return None
    lanes = run["flags"]["max_batch_size"]
    seconds = sum(
        s for label, s in trace["op_s"].items()
        if state_math.is_state_update(state_math.label_dims(label) or [], cfg, lanes))
    seated = metric_sum(run["after"], "engine_state_slots_in_use")
    dispatches = metric_delta(
        run["before"], run["after"], "engine_decode_step_seconds_count")
    if not seconds or not seated or not dispatches:
        return None
    policy = manifest.resolve_cell(run["cell"]).deployment["engine_policy"]
    calls_per_s = (dispatches / run["seconds"]
                   * (policy["tokens_per_dispatch"] - 1)
                   * state_math.mamba_layers(cfg))
    must_move = state_math.update_bytes_per_call(cfg, min(seated, lanes))
    device_s_per_s = seconds / trace["window_s"]
    return 100.0 * must_move * calls_per_s / device_s_per_s / peaks["hbm_bytes_per_s"]
