"""Share of attempted requests that met the mix's TTFT limit and its limit on the request's mean gap; a failed request misses."""

from kbench import stats

LAYER = "OpenAI surface"
UNIT = "%"
SOURCE = "host_clock"
MOVES = "ttft_mean_ms"


def read(run):
    if not run.get("limits"):
        return None
    return stats.slo_share(run["records"], run["seconds"], run["limits"])
