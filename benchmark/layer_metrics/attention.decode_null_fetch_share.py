"""The share of the decode kernel's walk that lies past its lanes' own lengths.

The decode kernel (`ops/pallas_paged_attention._decode_kernel`, under every one of its trace names) takes its lanes in blocks and walks each block out to its longest lane's page count: every lane of the block fetches a page and folds it in every iteration, the null page once it is past its own length.  100 x (1 - own / block) over the window's `engine_kv_decode_pages_total{reach}`: `own` = over the dispatches' decode steps, the pages the live lanes hold; `block` = over the kernel's blocks, lanes a block x the longest lane's pages, with the lanes dealt to blocks as the program deals them (in order of length since PR 42, which is what made the share small).  What is left is what a finer seating (fewer lanes a block) would take off the kernel's loop.  Counted on the host from the lanes' positions, whichever path decode attention takes.

A program without the counter (before PR 42) gives nothing to read."""

from kbench.server import metric_delta

LAYER = "attention kernels"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "tpot_p50_ms"


def read(run):
    before, after = run["before"], run["after"]
    own = metric_delta(before, after, "engine_kv_decode_pages_total", reach="own")
    block = metric_delta(before, after, "engine_kv_decode_pages_total", reach="block")
    if block <= 0:
        return None
    return 100.0 * (1.0 - own / block)
