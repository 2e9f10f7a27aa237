"""1 - the union of device-operation intervals over the traced window, averaged over the chips used."""

LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "output_tok_s"


def read(run):
    trace = run["trace"]
    if not trace or not trace.get("window_s"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
