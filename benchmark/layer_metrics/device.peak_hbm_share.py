"""peak_bytes_in_use of the fullest device over the chip's HBM."""

LAYER = "device"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "output_tok_s"


def read(run):
    peak = run["device"].get("memory_peak_bytes")
    if not run.get("peaks") or peak is None:
        return None
    return 100.0 * peak / run["peaks"]["hbm_bytes"]
