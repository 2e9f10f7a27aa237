"""Device time of the packed step's window attention kernel over device busy time.

Told by the kernel's name: a window layer that keeps a ring a lane reads (ring
pages, the buffer's own slice) in the packed step through ONE kernel, called
`window_attention_ragged` (`window_attention_decode`, the decode steps' kernel
over the ring, is `attention.window_share`).  Returns nothing where no such
kernel ran (the XLA form of a short window, a program from before the kernel)."""

from kbench import cohere_math, state_math

LAYER = "attention kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "tpot_p50_ms"


def read(run):
    return state_math.share_of_labels(
        run["trace"],
        lambda label: label.startswith(cohere_math.WINDOW_RAGGED_KERNEL))
