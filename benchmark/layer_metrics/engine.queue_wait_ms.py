"""Median of received -> admitted on the engine's own clock (/admin/telemetry, its rolling ring at the window's close)."""

LAYER = "scheduler"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "ttft_mean_ms"


def read(run):
    p50 = (run["telemetry"].get("queue_wait_s") or {}).get("p50")
    return None if p50 is None else p50 * 1e3
