"""How late the generator ran: 99th percentile of (send time - due time) over the window's requests.

A starved generator must not be read as a fast server."""

from kbench import stats

LAYER = "load generator"
UNIT = "ms"
SOURCE = "host_clock"
MOVES = "ttft_mean_ms"


def read(run):
    late = stats.late_ms(run["records"], run["seconds"])
    return stats.percentile(late, 99) if late else None
