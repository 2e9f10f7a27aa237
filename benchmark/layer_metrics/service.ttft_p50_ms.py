"""Median time to first token at the client's socket, from the due time."""

LAYER = "OpenAI surface"
UNIT = "ms"
SOURCE = "host_clock"
MOVES = "ttft_mean_ms"


def read(run):
    return run["client"]["ttft_p50_ms"][0]
