"""95th percentile of time to first token at the client's socket.

Recorded, not judged: it lies where a few percent of requests spread over a whole dispatch period (PERF.md section 2)."""

LAYER = "OpenAI surface"
UNIT = "ms"
SOURCE = "host_clock"
MOVES = "ttft_mean_ms"


def read(run):
    return run["client"]["ttft_p95_ms"][0]
