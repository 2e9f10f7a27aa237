"""Device time of the latent decode kernel (`latent_attention_decode*`: one query token a lane over its latent pages) over device busy time.

The packed step's attention (`latent_attention_ragged*`) is not in it: `kernel.attention_share` reads both.  Returns nothing for a configuration without latent attention or a trace in which the kernel did not run."""

from kbench import latent_math

LAYER = "attention kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "tpot_p50_ms"


def read(run):
    trace = run["trace"]
    if (not trace or not trace.get("busy_s")
            or not latent_math.is_latent(run["hf_config"])):
        return None
    seconds = latent_math.kernel_seconds(trace)
    return 100.0 * seconds / trace["busy_s"] if seconds else None
