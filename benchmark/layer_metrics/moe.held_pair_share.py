"""(token, expert) pairs this chip multiplied over the pairs routed, over the window: `engine_moe_assignments_total` over it plus `engine_moe_pairs_elsewhere_total`.

Both are the program's own sums over the rows each expert layer saw (a layer behind the last layer that writes state sees one row a lane in the packed step, not every token).  Near the share of the experts held (64 of 128: 52.1 % on the chip with seeded random routers, PERF.md section 6); 100 where every expert is held.  A program without the fourth counter gives nothing to read."""

from kbench import loop_math
from kbench.server import metric_delta

LAYER = "expert layers"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "output_tok_s"


def read(run):
    before, after = run["before"], run["after"]
    if not loop_math.has_series(after, "engine_moe_pairs_elsewhere_total"):
        return None
    here = metric_delta(before, after, "engine_moe_assignments_total")
    away = metric_delta(before, after, "engine_moe_pairs_elsewhere_total")
    if here + away <= 0:
        return None
    return 100.0 * here / (here + away)
