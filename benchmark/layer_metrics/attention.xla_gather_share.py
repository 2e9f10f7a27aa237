"""Device time of the XLA attention path's page gather and its copies over device busy time.

Where a dispatch's page table is narrower than the program's
PALLAS_MIN_PAGES, attention runs as plain XLA: every lane's KV pages are
gathered out of the cache and copied, then multiplied.  Those operations
are told by what they produce: arrays whose last three dimensions are one
page's (KV heads on this chip, page size, head size) and whose first is not
the whole cache's page count.  `kernel.attention_share` reads the Pallas
kernels only; in a cell that takes this path it is this metric that says
what attention costs."""

import re

LAYER = "attention kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "tpot_p50_ms"

_DIMS = re.compile(r"_[a-z]+\d*_((?:\d+_)+)$")


def read(run):
    trace = run["trace"]
    if not trace or not trace.get("busy_s"):
        return None
    cfg, flags = run["hf_config"], run["flags"]
    page = [cfg["num_key_value_heads"] // flags.get("tp", 1),
            flags["page_size"], cfg["head_dim"]]
    picked = 0.0
    for label, seconds in trace["op_s"].items():
        m = _DIMS.search(label)
        if not m:
            continue
        dims = [int(d) for d in m.group(1).split("_") if d]
        if len(dims) > 3 and dims[-3:] == page and dims[0] != flags["kv_pages"]:
            picked += seconds
    return 100.0 * picked / trace["busy_s"]
