"""95th percentile over requests of the request's mean gap between tokens (requests with at least 16 gaps inside the window)."""

LAYER = "OpenAI surface"
UNIT = "ms"
SOURCE = "host_clock"
MOVES = "tpot_p50_ms"


def read(run):
    return run["client"]["tpot_p95_ms"][0]
