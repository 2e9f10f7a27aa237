"""Device time of the Mamba layers' scan and convolution operations over device busy time.

Told by what an operation produces: the scan's state [.., d_inner, d_state]
(the decode step's [lanes, ..] and the packed buffer's [blocks, ..]) or the
convolution's window or tail [.., d_conv or d_conv - 1, d_inner].  The
projections around them are matrix multiplications and are not counted.
Returns nothing for a configuration without Mamba sizes or a trace without such operations."""

from kbench import state_math

LAYER = "state-space layers"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "tpot_p50_ms"


def read(run):
    cfg = run["hf_config"]
    if "mamba_d_inner" not in cfg:
        return None
    return state_math.share_of_labels(
        run["trace"],
        lambda label: state_math.is_ssm(state_math.label_dims(label) or [], cfg))
