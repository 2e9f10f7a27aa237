"""Host-RAM / disk KV tiers with lru / arc eviction between them.

This is the spill engine, folded
into the hierarchical store (docs/kv_hierarchy.md) and made
**clock-injectable**: every entry stamp comes from a resilience.Clock so
spill traffic inside the fleet simulator stays a pure function of
virtual time (the module used to call ``time.monotonic`` directly, which
broke the byte-identical-per-seed contract whenever a scenario spilled).

Parity: KVCacheOffloadingSpec (ref llm_inference_service_types.go:188-260
— CPU + disk tiers with lru/arc eviction policies).  The engine spills a
preempted sequence's KV pages here (engine.py _preempt) and re-injects on
resume; entries the store had to drop simply re-prefill — dropping is a
performance event, never a correctness one.

Payloads are dicts of numpy arrays (one entry per tensor), which makes
the quantized (int8 pages + scales) cache a first-class payload rather
than a rejected configuration.  Disk entries are .npz files under
`disk_dir`; host->disk demotion is the eviction path, disk-full drops
the policy's coldest disk entry.

Eviction policies:
- lru: strict recency (OrderedDict order, refreshed on touch).
- arc: the adaptive T1/T2 + B1/B2 ghost-list scheme — T1 holds
  seen-once entries, T2 seen-again; ghost hits adapt the T1 target
  size `p`.  For spill/resume traffic this behaves like LRU until
  resumed-and-respilled sequences (seen-again) exist, which it then
  protects over one-shot spills.
"""

from __future__ import annotations

import os
import shutil
import uuid
from collections import OrderedDict
from dataclasses import dataclass
from typing import Callable, Dict, Optional

import numpy as np

from ..logging import logger
from ..resilience import MONOTONIC, Clock

Payload = Dict[str, np.ndarray]


def payload_nbytes(payload: Payload) -> int:
    return int(sum(a.nbytes for a in payload.values()))


@dataclass
class TierConfig:
    host_bytes: int = 0
    disk_bytes: int = 0
    disk_dir: str = "/tmp/kserve-tpu-kv"
    policy: str = "lru"  # lru | arc


@dataclass
class _Entry:
    nbytes: int
    tier: str  # "host" | "disk"
    payload: Optional[Payload] = None  # host tier
    path: Optional[str] = None  # disk tier
    hits: int = 0
    stored_at: float = 0.0  # stamped from the injected clock


class _ARCState:
    """Ghost lists + adaptation for the arc policy (keys only)."""

    def __init__(self):
        self.t1: "OrderedDict[str, None]" = OrderedDict()  # seen once
        self.t2: "OrderedDict[str, None]" = OrderedDict()  # seen again
        self.b1: "OrderedDict[str, None]" = OrderedDict()  # ghosts of t1
        self.b2: "OrderedDict[str, None]" = OrderedDict()  # ghosts of t2
        self.p = 0.0  # target fraction of capacity for t1

    def on_insert(self, key: str) -> None:
        if key in self.b1:
            # ghost hit in b1: recency is winning — grow t1's share
            self.p = min(1.0, self.p + max(1.0 / max(len(self.b1), 1), 0.05))
            del self.b1[key]
            self.t2[key] = None
        elif key in self.b2:
            self.p = max(0.0, self.p - max(1.0 / max(len(self.b2), 1), 0.05))
            del self.b2[key]
            self.t2[key] = None
        elif key in self.t1:
            del self.t1[key]
            self.t2[key] = None
        elif key in self.t2:
            self.t2.move_to_end(key)
        else:
            self.t1[key] = None

    def on_touch(self, key: str) -> None:
        if key in self.t1:
            del self.t1[key]
            self.t2[key] = None
        elif key in self.t2:
            self.t2.move_to_end(key)

    def pick_victim(self, resident) -> Optional[str]:
        """Coldest resident key: from t1 while it exceeds its target
        share, else from t2 (LRU within each list)."""
        t1_resident = [k for k in self.t1 if k in resident]
        t2_resident = [k for k in self.t2 if k in resident]
        total = len(t1_resident) + len(t2_resident)
        if not total:
            return None
        want_t1 = self.p * total
        if t1_resident and (len(t1_resident) > want_t1 or not t2_resident):
            victim = t1_resident[0]
            del self.t1[victim]
            self.b1[victim] = None
            while len(self.b1) > 512:
                self.b1.popitem(last=False)
            return victim
        victim = t2_resident[0]
        del self.t2[victim]
        self.b2[victim] = None
        while len(self.b2) > 512:
            self.b2.popitem(last=False)
        return victim

    def forget(self, key: str) -> None:
        for lst in (self.t1, self.t2, self.b1, self.b2):
            lst.pop(key, None)


class KVTierStore:
    """The host/disk tier pair.  `on_event(tier, event)` (optional) is the
    observability seam the hierarchical store wires to
    ``kv_tier_events_total`` — demotions and pressure drops happen deep
    inside the eviction cascade, so the hook lives here."""

    def __init__(self, config: TierConfig, clock: Clock = MONOTONIC,
                 on_event: Optional[Callable[[str, str], None]] = None):
        if config.policy not in ("lru", "arc"):
            raise ValueError(f"unknown eviction policy {config.policy!r}")
        self.config = config
        self.clock = clock
        self._on_event = on_event
        self._entries: "OrderedDict[str, _Entry]" = OrderedDict()
        self.host_used = 0
        self.disk_used = 0
        self._arc = _ARCState() if config.policy == "arc" else None
        self._dir: Optional[str] = None
        self.drops = 0  # entries lost to pressure (resume re-prefills)

    # ---------------- internals ----------------

    def _event(self, tier: str, event: str) -> None:
        if self._on_event is not None:
            self._on_event(tier, event)

    def _ensure_dir(self) -> str:
        if self._dir is None:
            self._sweep_stale_dirs()
            path = os.path.join(
                self.config.disk_dir, f"kv-{os.getpid()}-{uuid.uuid4().hex[:8]}")
            os.makedirs(path, exist_ok=True)
            self._dir = path
        return self._dir

    def _sweep_stale_dirs(self) -> None:
        """Remove spill dirs left by DEAD processes.  Spill files are only
        unlinked by in-memory accounting, so a crashed pod leaks its
        kv-<pid>-<rand> subdir; on a persistent volume (PVC tier) those
        leaks accumulate across restarts until the claim fills and
        np.savez dies with ENOSPC.  A dir whose embedded pid is still
        alive (a concurrent engine on a shared RWX claim) is left alone."""
        import re as _re
        import shutil as _shutil

        try:
            names = os.listdir(self.config.disk_dir)
        except OSError:
            return
        for name in names:
            m = _re.fullmatch(r"kv-(\d+)-[0-9a-f]+", name)
            if not m:
                continue
            pid = int(m.group(1))
            alive = True
            try:
                os.kill(pid, 0)
            except ProcessLookupError:
                alive = False
            except PermissionError:
                pass  # exists, owned by someone else: alive
            if alive:
                # a live process — possibly another store in THIS process
                # (dp replicas share the dir): never touch it
                continue
            _shutil.rmtree(
                os.path.join(self.config.disk_dir, name), ignore_errors=True)

    def _pick_host_victim(self) -> Optional[str]:
        host = {k for k, e in self._entries.items() if e.tier == "host"}
        if not host:
            return None
        if self._arc is not None:
            victim = self._arc.pick_victim(host)
            if victim is not None:
                return victim
        for k in self._entries:  # insertion/touch order = LRU
            if k in host:
                return k
        return None

    def _demote_to_disk(self, key: str) -> bool:
        entry = self._entries[key]
        if self.config.disk_bytes <= 0:
            return False
        while self.disk_used + entry.nbytes > self.config.disk_bytes:
            disk_keys = [k for k, e in self._entries.items()
                         if e.tier == "disk"]
            if not disk_keys:
                return False
            self._drop(disk_keys[0])
        path = os.path.join(self._ensure_dir(), f"{uuid.uuid4().hex}.npz")
        np.savez(path, **entry.payload)
        entry.path = path
        entry.payload = None
        entry.tier = "disk"
        self.host_used -= entry.nbytes
        self.disk_used += entry.nbytes
        self._event("disk", "demote")
        return True

    def _drop(self, key: str) -> None:
        entry = self._entries.pop(key, None)
        if entry is None:
            return
        if entry.tier == "host":
            self.host_used -= entry.nbytes
        else:
            self.disk_used -= entry.nbytes
            if entry.path:
                try:
                    os.unlink(entry.path)
                except OSError:
                    pass
        if self._arc is not None:
            self._arc.forget(key)
        self.drops += 1
        self._event(entry.tier, "drop")
        logger.debug("kv tier store dropped %s under pressure", key)

    # ---------------- public API ----------------

    def put(self, key: str, payload: Payload) -> bool:
        """Store (host-first).  False = didn't fit anywhere; the caller
        falls back to recompute-on-resume."""
        nbytes = payload_nbytes(payload)
        if key in self._entries:
            self.discard(key)
        if nbytes > max(self.config.host_bytes, self.config.disk_bytes):
            return False
        # make room in host by demoting cold entries to disk
        while self.host_used + nbytes > self.config.host_bytes:
            victim = self._pick_host_victim()
            if victim is None:
                break
            if not self._demote_to_disk(victim):
                self._drop(victim)
        entry = _Entry(nbytes=nbytes, tier="host", payload=payload,
                       stored_at=self.clock.now())
        if self.host_used + nbytes <= self.config.host_bytes:
            self._entries[key] = entry
            self.host_used += nbytes
        elif self.config.disk_bytes > 0:
            self._entries[key] = entry
            self.host_used += nbytes
            if not self._demote_to_disk(key):
                self._entries.pop(key, None)
                self.host_used -= nbytes
                return False
        else:
            return False
        if self._arc is not None:
            self._arc.on_insert(key)
        return True

    def contains(self, key: str) -> bool:
        return key in self._entries

    def keys(self) -> list:
        return list(self._entries)

    def tier_of(self, key: str) -> Optional[str]:
        entry = self._entries.get(key)
        return entry.tier if entry is not None else None

    def would_fit(self, nbytes: int) -> bool:
        """Upper-bound pre-check so callers skip the device gather when a
        payload can never be stored (eviction can free everything else)."""
        return nbytes <= max(self.config.host_bytes, self.config.disk_bytes)

    def get(self, key: str, consume: bool = True) -> Optional[Payload]:
        """Fetch an entry.  ``consume=True`` (the spill contract: resume
        consumes the spill) removes it; ``consume=False`` (the prefix
        contract: a tier-resident page may be paged in again after the
        next HBM eviction) leaves it resident and refreshes recency."""
        if not consume:
            entry = self._entries.get(key)
            if entry is None:
                return None
            entry.hits += 1
            self._entries.move_to_end(key)
            if self._arc is not None:
                self._arc.on_touch(key)
            if entry.tier == "host":
                return entry.payload
            try:
                with np.load(entry.path) as data:
                    return {k: data[k] for k in data.files}
            except (OSError, ValueError):
                logger.warning("kv disk tier read failed for %s", key)
                self._drop(key)
                return None
        entry = self._entries.pop(key, None)
        if entry is None:
            return None
        if self._arc is not None:
            self._arc.on_touch(key)
        if entry.tier == "host":
            self.host_used -= entry.nbytes
            return entry.payload
        self.disk_used -= entry.nbytes
        try:
            with np.load(entry.path) as data:
                return {k: data[k] for k in data.files}
        except (OSError, ValueError):
            logger.warning("kv disk tier read failed for %s", key)
            return None
        finally:
            try:
                os.unlink(entry.path)
            except OSError:
                pass

    def discard(self, key: str) -> None:
        entry = self._entries.pop(key, None)
        if entry is None:
            return
        if entry.tier == "host":
            self.host_used -= entry.nbytes
        else:
            self.disk_used -= entry.nbytes
            if entry.path:
                try:
                    os.unlink(entry.path)
                except OSError:
                    pass
        if self._arc is not None:
            self._arc.forget(key)

    def close(self) -> None:
        if self._dir is not None:
            shutil.rmtree(self._dir, ignore_errors=True)
            self._dir = None
