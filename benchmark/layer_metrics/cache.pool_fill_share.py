"""Pages of the pool held over the pages it has, mean of the window's two ends: how near the lanes are to the limit that sets their number.

Held = `engine_kv_pages_total` - `engine_kv_pages_free`: the lanes' pages and those the prefix cache keeps until pressure takes them back.  A program without `engine_kv_pages_total` gives nothing to read."""

from kbench import loop_math
from kbench.server import metric_sum

LAYER = "cache manager"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "output_tok_s"


def read(run):
    shares = []
    for snapshot in (run["before"], run["after"]):
        if not loop_math.has_series(snapshot, "engine_kv_pages_total"):
            return None
        total = metric_sum(snapshot, "engine_kv_pages_total")
        if total <= 0:
            return None
        shares.append(1.0 - metric_sum(snapshot, "engine_kv_pages_free") / total)
    return 100.0 * sum(shares) / len(shares)
