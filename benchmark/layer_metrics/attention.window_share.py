"""Device time of the window layers' attention kernel over device busy time.

Told by the kernel's name: the program calls the decode kernel over a
lane's ring as `window_attention_decode`.  The packed buffer's window
attention is plain XLA (a gather of the ring per block of queries) and is
not told apart here.  Returns nothing where no such kernel ran."""

from kbench import state_math

LAYER = "attention kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "tpot_p50_ms"

KERNEL = "window_attention_decode"


def read(run):
    return state_math.share_of_labels(
        run["trace"], lambda label: label.startswith(KERNEL))
