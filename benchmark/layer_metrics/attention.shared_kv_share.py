"""Device time of the attention kernel over the shared full-attention cache over device busy time.

Told by the kernel's name: the full-attention layer and the cross-attention
layers that reuse its K/V call the decode kernel as
`shared_kv_attention_decode`, in the decode steps and (one query per lane)
in the packed step.  Returns nothing where no such kernel ran."""

from kbench import state_math

LAYER = "attention kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "tpot_p50_ms"

KERNEL = "shared_kv_attention_decode"


def read(run):
    return state_math.share_of_labels(
        run["trace"], lambda label: label.startswith(KERNEL))
