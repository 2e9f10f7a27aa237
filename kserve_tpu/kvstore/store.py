"""The hierarchical KV page store: spill + prefix paths over one tier
pair, plus the content-addressed persistent prefix layer.

``HierarchicalKVStore`` is what the engine holds (engine.py builds one
whenever host/disk offload OR the persistent prefix layer is
configured).  Two key namespaces share the host/disk tiers:

- **spill entries** (request-id keys, consume-on-get): a preempted
  sequence's whole KV, re-injected on resume (kvstore/tiers.py);
- **prefix entries** (``px-<digest hex>`` keys, non-consuming): single
  prefix-cache pages demoted out of HBM instead of dropped, readable
  any number of times (the same page can be paged back in after every
  HBM eviction).

The persistent layer (kvstore/persist.py) sits below both as a
prefix-only durable tier: demoted or reused prefix pages are written
through as digest-named files, and a fresh process indexes them at
construction — the resident-digest set a woken replica advertises (and
serves) before it has prefilled anything.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from ..logging import logger
from ..metrics import KV_TIER_EVENTS
from ..resilience import MONOTONIC, Clock
from .peer import digest_set_wire, encode_page
from .persist import PersistentPrefixStore
from .tiers import KVTierStore, Payload, TierConfig, payload_nbytes

_PX = "px-"  # prefix-entry key namespace inside the shared tier store


@dataclass
class KVStoreConfig:
    host_bytes: int = 0
    disk_bytes: int = 0
    disk_dir: str = "/tmp/kserve-tpu-kv"
    policy: str = "lru"  # lru | arc
    persist_dir: Optional[str] = None  # content-addressed prefix files


@dataclass
class PrefixStoreStats:
    """Per-replica prefix-store accounting, exported through
    ``engine.scheduler_state()`` -> REST ``/state`` -> the EPP fleet
    block (the first cut of the global prefix index, ROADMAP item 2)."""

    hits: int = 0  # longest_prefix_run queries that found >= 1 page
    misses: int = 0  # queries that found nothing tier-resident
    demotions: int = 0  # HBM prefix pages demoted into the tiers
    pageins: int = 0  # pages promoted tier -> device
    pagein_tokens: int = 0  # tokens those pages cover
    pagein_tokens_by_tier: Dict[str, int] = field(default_factory=dict)
    persist_writes: int = 0  # digest files written through
    corrupt: int = 0  # persistent entries that failed to read back
    drops: int = 0  # prefix pages lost under tier pressure

    def as_dict(self) -> Dict:
        return {
            "hits": self.hits,
            "misses": self.misses,
            "demotions": self.demotions,
            "pageins": self.pageins,
            "pagein_tokens": self.pagein_tokens,
            "pagein_tokens_by_tier": dict(self.pagein_tokens_by_tier),
            "persist_writes": self.persist_writes,
            "corrupt": self.corrupt,
            "drops": self.drops,
        }


class HierarchicalKVStore:
    """Thread contract: the engine loop owns every mutation EXCEPT
    ``get_prefix``, which the async page-in path runs on the fetch worker
    (kvstore reads overlap decode — the point of the seam).  One lock
    therefore guards all tier/persist state; hold times are dict ops plus
    at worst one page-file read, so loop-side contention is bounded by a
    single page I/O."""

    def __init__(self, config: KVStoreConfig, clock: Clock = MONOTONIC):
        self.config = config
        self.stats = PrefixStoreStats()
        self._lock = threading.RLock()
        self.tiers = KVTierStore(
            TierConfig(
                host_bytes=config.host_bytes,
                disk_bytes=config.disk_bytes,
                disk_dir=config.disk_dir,
                policy=config.policy,
            ),
            clock=clock,
            on_event=self._on_tier_event,
        )
        self.persist: Optional[PersistentPrefixStore] = None
        if config.persist_dir:
            self.persist = PersistentPrefixStore(
                config.persist_dir, on_event=self._on_persist_event)
            if len(self.persist):
                logger.info(
                    "kv persistent prefix store indexed: %d digest(s) "
                    "under %s", len(self.persist), config.persist_dir)

    # ---------------- events / accounting ----------------

    def _on_tier_event(self, tier: str, event: str) -> None:
        KV_TIER_EVENTS.labels(tier=tier, event=event).inc()
        if event == "drop":
            self.stats.drops += 1

    def _on_persist_event(self, tier: str, event: str) -> None:
        KV_TIER_EVENTS.labels(tier=tier, event=event).inc()
        if event == "store":
            self.stats.persist_writes += 1
        elif event == "corrupt":
            self.stats.corrupt += 1

    @property
    def host_used(self) -> int:
        return self.tiers.host_used

    @property
    def disk_used(self) -> int:
        return self.tiers.disk_used

    def resident_prefix_digests(self) -> int:
        """Digest count resident anywhere below HBM (tiered + persistent,
        deduplicated) — the replica's advertised prefix-store footprint."""
        with self._lock:
            tiered = {k for k in self.tiers.keys() if k.startswith(_PX)}
            if self.persist is not None:
                tiered |= {_PX + d.hex() for d in self.persist.digests()}
            return len(tiered)

    def stats_dict(self) -> Dict:
        out = self.stats.as_dict()
        out["resident_digests"] = self.resident_prefix_digests()
        out["persist_digests"] = (
            len(self.persist) if self.persist is not None else 0
        )
        return out

    # ---------------- spill API (engine preemption contract) ----------------

    def put(self, key: str, payload: Payload) -> bool:
        with self._lock:
            return self.tiers.put(key, payload)

    def get(self, key: str) -> Optional[Payload]:
        """Fetch AND remove (resume consumes the spill)."""
        with self._lock:
            return self.tiers.get(key, consume=True)

    def contains(self, key: str) -> bool:
        with self._lock:
            return self.tiers.contains(key)

    def discard(self, key: str) -> None:
        with self._lock:
            self.tiers.discard(key)

    def would_fit(self, nbytes: int) -> bool:
        return self.tiers.would_fit(nbytes)

    # ---------------- prefix API (digest-chained pages) ----------------

    @property
    def accepts_prefix_pages(self) -> bool:
        """Anywhere below HBM for an evicted prefix page to land."""
        return (
            self.config.host_bytes > 0
            or self.config.disk_bytes > 0
            or self.persist is not None
        )

    def put_prefix(self, digest: bytes, payload: Payload,
                   persist: bool = True) -> bool:
        """Demote/write-through one prefix page.  Tier placement is
        best-effort (host-first, disk cascade); the persistent layer gets
        an independent write-through when enabled.  False = the page
        landed nowhere (a drop: the next use re-prefills)."""
        stored = False
        key = _PX + digest.hex()
        with self._lock:
            if self.config.host_bytes > 0 or self.config.disk_bytes > 0:
                if self.tiers.put(key, payload):
                    stored = True
            if persist and self.persist is not None:
                if self.persist.store(digest, payload):
                    stored = True
        return stored

    def record_demotion(self, n_pages: int) -> None:
        self.stats.demotions += n_pages
        if n_pages:
            KV_TIER_EVENTS.labels(tier="host", event="demote").inc(n_pages)

    def prefix_tier_of(self, digest: bytes) -> Optional[str]:
        with self._lock:
            tier = self.tiers.tier_of(_PX + digest.hex())
            if tier is not None:
                return tier
            if self.persist is not None and digest in self.persist:
                return "persist"
            return None

    def longest_prefix_run(
        self, digests: Sequence[bytes],
    ) -> List[Tuple[bytes, str]]:
        """Longest leading run of tier-resident digests: [(digest, tier)]
        — what admission pages in before prefilling only the uncached
        tail.  Counts a hit/miss on every non-trivial query (the rate the
        EPP fleet block exports)."""
        run: List[Tuple[bytes, str]] = []
        with self._lock:
            for digest in digests:
                tier = self.prefix_tier_of(digest)
                if tier is None:
                    break
                run.append((digest, tier))
        if digests:
            if run:
                self.stats.hits += 1
            else:
                self.stats.misses += 1
        return run

    def get_prefix(self, digest: bytes) -> Optional[Tuple[Payload, str]]:
        """Read one prefix page (non-consuming): (payload, source tier),
        or None when it is gone / unreadable (the run truncates and the
        tail re-prefills)."""
        with self._lock:
            key = _PX + digest.hex()
            tier = self.tiers.tier_of(key)
            if tier is not None:
                payload = self.tiers.get(key, consume=False)
                if payload is not None:
                    return payload, tier
            if self.persist is not None:
                payload = self.persist.load(digest)
                if payload is not None:
                    return payload, "persist"
            return None

    def record_pagein(self, pages_by_tier: Dict[str, int],
                      tokens_by_tier: Dict[str, int]) -> None:
        for tier, n in pages_by_tier.items():
            if n:
                KV_TIER_EVENTS.labels(tier=tier, event="pagein").inc(n)
            self.stats.pageins += n
        for tier, t in tokens_by_tier.items():
            self.stats.pagein_tokens += t
            self.stats.pagein_tokens_by_tier[tier] = (
                self.stats.pagein_tokens_by_tier.get(tier, 0) + t)

    # ---------------- peer fabric (kvstore/peer.py) ----------------

    def read_peer_page(self, digest: bytes) -> Optional[bytes]:
        """Wire-encoded page bytes for the peer page server, or None when
        the digest is not durably held here.  Only PERSIST entries are
        served: they are the content-addressed files whose bytes the wire
        trailer binds to the digest, and the only tier a peer's index
        learns about (resident_digest_wire below)."""
        if self.persist is None:
            return None
        raw = self.persist.read_page_bytes(digest)
        if raw is None:
            return None
        return encode_page(digest, raw)

    def resident_digest_wire(self) -> Optional[Dict]:
        """The bounded, generation-stamped digest-set summary this
        replica advertises (scheduler_state -> EPP /state -> peers'
        PeerPageIndex), or None when the persistent layer is off."""
        if self.persist is None:
            return None
        with self._lock:
            return digest_set_wire(
                self.persist.generation, self.persist.digests())

    def needs_persist(self, digests: Sequence[bytes]) -> List[bytes]:
        """The subset of `digests` not yet in the persistent layer (the
        persist-on-reuse trigger: a prefix HIT proves the pages are worth
        keeping across restarts)."""
        if self.persist is None or not self.persist.writable:
            return []
        with self._lock:
            return [d for d in digests if d not in self.persist]

    def close(self) -> None:
        with self._lock:
            self.tiers.close()


__all__ = [
    "HierarchicalKVStore",
    "KVStoreConfig",
    "PrefixStoreStats",
    "payload_nbytes",
]
