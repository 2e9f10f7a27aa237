"""Hierarchical KV page store (docs/kv_hierarchy.md).

One store unifies the two host-side KV paths that used to live apart:

- the **spill path** (preempted sequences park their device KV in host
  RAM / disk and re-inject on resume — formerly an engine module),
- the **prefix path** (evicted prefix-cache pages demote into the same
  tiers instead of being dropped, keyed by the blake2b digest chains of
  scheduler/prefix.py, plus a content-addressed persistent layer whose
  digest-named files survive process restarts — the hot-wake story).

Tier order is HBM (engine/prefix_cache.py, outside this package) ->
pinned host RAM -> node-local disk -> persistent prefix files next to
the AOT executable cache.  A page dropped anywhere in the hierarchy is
a performance event, never a correctness one: the engine re-prefills.
"""

from .peer import (
    PAGE_ROUTE,
    PageVerifyError,
    PeerPageClient,
    PeerPageIndex,
    decode_page,
    decode_payload,
    digest_set_wire,
    encode_page,
)
from .persist import PersistentPrefixStore
from .store import HierarchicalKVStore, KVStoreConfig, PrefixStoreStats
from .tiers import KVTierStore, Payload, TierConfig, payload_nbytes

__all__ = [
    "HierarchicalKVStore",
    "KVStoreConfig",
    "KVTierStore",
    "PAGE_ROUTE",
    "PageVerifyError",
    "Payload",
    "PeerPageClient",
    "PeerPageIndex",
    "PersistentPrefixStore",
    "PrefixStoreStats",
    "TierConfig",
    "decode_page",
    "decode_payload",
    "digest_set_wire",
    "encode_page",
    "payload_nbytes",
]
