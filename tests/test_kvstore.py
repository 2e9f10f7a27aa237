"""Hierarchical KV store tests (kserve_tpu/kvstore, docs/kv_hierarchy.md):
clock-injectable host/disk tiers, the content-addressed persistent prefix
layer, demotion of evicted prefix pages, async tier->device page-in, the
hot-wake restart proof, checkpoint resume through the store, and the
prefix-store stats flow engine -> picker -> FleetSignals."""

import asyncio
import os
import time

import numpy as np
import pytest

from conftest import async_test


async def wait_until(cond, timeout_s: float = 10.0):
    """Spin the loop until `cond()` (async persist/page-in tasks ride the
    real fetch worker thread here, so completion is not one yield away)."""
    t0 = time.monotonic()
    while not cond():
        assert time.monotonic() - t0 < timeout_s, "condition never held"
        await asyncio.sleep(0.01)

from kserve_tpu.engine.sampling import SamplingParams
from kserve_tpu.kvstore import (
    HierarchicalKVStore,
    KVStoreConfig,
    KVTierStore,
    PersistentPrefixStore,
    TierConfig,
)
from kserve_tpu.resilience import MONOTONIC, Deadline, FakeClock

from test_engine import collect, make_engine

D1 = b"\x01" * 16
D2 = b"\x02" * 16
D3 = b"\x03" * 16


def page_payload(fill=1.0):
    return {"kv": np.full((2, 1, 2, 2, 8, 4), fill, np.float32)}


class TestTierClockInjection:
    def test_entry_stamps_come_from_injected_clock(self, tmp_path):
        """kv_tiers used to read time.monotonic directly — under the fleet
        sim that broke byte-identical-per-seed whenever spill traffic
        landed.  Entry stamps must come from the injected clock."""
        clock = FakeClock()
        clock.advance(123.5)
        store = KVTierStore(
            TierConfig(host_bytes=1 << 20, disk_dir=str(tmp_path)),
            clock=clock)
        store.put("a", page_payload())
        assert store._entries["a"].stored_at == clock.now()
        clock.advance(10.0)
        store.put("b", page_payload())
        assert store._entries["b"].stored_at == clock.now()
        assert store._entries["b"].stored_at - store._entries["a"].stored_at \
            == pytest.approx(10.0)

    def test_non_consuming_get_leaves_entry_resident(self, tmp_path):
        store = KVTierStore(
            TierConfig(host_bytes=1 << 20, disk_bytes=1 << 20,
                       disk_dir=str(tmp_path)))
        store.put("px-aa", page_payload(2.0))
        for _ in range(3):  # readable any number of times
            got = store.get("px-aa", consume=False)
            assert got is not None and got["kv"][0, 0, 0, 0, 0, 0] == 2.0
        assert store.contains("px-aa")
        # the spill contract still consumes
        assert store.get("px-aa") is not None
        assert not store.contains("px-aa")


class TestPersistentPrefixStore:
    def test_round_trip_and_index_across_instances(self, tmp_path):
        store = PersistentPrefixStore(str(tmp_path))
        assert store.store(D1, page_payload(3.0))
        assert D1 in store
        # content-addressed: second store is a no-op, not a rewrite
        path = os.path.join(str(tmp_path), f"px-{D1.hex()}.kvpage")
        mtime = os.path.getmtime(path)
        assert store.store(D1, page_payload(9.0))
        assert os.path.getmtime(path) == mtime
        # no torn/tmp files left behind
        assert not [n for n in os.listdir(str(tmp_path))
                    if n.endswith(".tmp")]
        # a fresh process indexes the directory
        store2 = PersistentPrefixStore(str(tmp_path))
        assert len(store2) == 1 and D1 in store2
        got = store2.load(D1)
        assert got is not None
        np.testing.assert_array_equal(got["kv"], page_payload(3.0)["kv"])

    def test_corrupt_entry_reads_as_miss_and_is_removed(self, tmp_path):
        store = PersistentPrefixStore(str(tmp_path))
        store.store(D1, page_payload())
        path = os.path.join(str(tmp_path), f"px-{D1.hex()}.kvpage")
        with open(path, "wb") as f:
            f.write(b"torn garbage, not an npz")
        store2 = PersistentPrefixStore(str(tmp_path))
        assert store2.load(D1) is None  # logged miss, never a crash
        assert not os.path.exists(path), "corrupt entry must be unlinked"
        assert store2.load(D1) is None  # and stays a plain miss

    def test_foreign_files_ignored(self, tmp_path):
        with open(os.path.join(str(tmp_path), "meta.json"), "w") as f:
            f.write("{}")
        with open(os.path.join(str(tmp_path), "px-zzzz.kvpage"), "w") as f:
            f.write("not hex")
        store = PersistentPrefixStore(str(tmp_path))
        assert len(store) == 0

    def test_unwritable_root_degrades_to_noop(self, tmp_path):
        blocked = os.path.join(str(tmp_path), "file-not-dir")
        with open(blocked, "w") as f:
            f.write("x")
        store = PersistentPrefixStore(os.path.join(blocked, "sub"))
        assert not store.writable
        assert not store.store(D1, page_payload())
        assert store.load(D1) is None

    def test_corrupt_entry_on_readonly_volume_misses_without_crash(
            self, tmp_path, monkeypatch):
        """ISSUE 19 satellite: a corrupt entry whose unlink fails (the
        cache volume went read-only underneath us) must read as a plain
        miss, stay a miss, and flip the store to read-only — never
        crash, never retry the unlink forever."""
        events = []
        store = PersistentPrefixStore(
            str(tmp_path), on_event=lambda tier, ev: events.append(ev))
        store.store(D1, page_payload())
        path = os.path.join(str(tmp_path), f"px-{D1.hex()}.kvpage")
        with open(path, "wb") as f:
            f.write(b"torn garbage, not an npz")
        gen = store.generation

        def ro_unlink(p):
            raise OSError(30, "Read-only file system", p)

        monkeypatch.setattr(os, "unlink", ro_unlink)
        assert store.load(D1) is None
        assert "corrupt" in events
        # the file could not be removed, but the in-memory index did
        # forget it: subsequent loads are clean misses, not re-parses
        assert os.path.exists(path)
        assert store.load(D1) is None
        assert events.count("corrupt") == 1
        assert store.generation == gen + 1
        # and the store stopped pretending the volume is writable
        assert not store.writable
        monkeypatch.undo()
        assert not store.store(D2, page_payload())


class TestHierarchicalStore:
    def _store(self, tmp_path, host=1 << 20, persist=True):
        return HierarchicalKVStore(KVStoreConfig(
            host_bytes=host,
            disk_dir=os.path.join(str(tmp_path), "disk"),
            persist_dir=os.path.join(str(tmp_path), "px") if persist
            else None,
        ))

    def test_longest_run_spans_tiers_and_truncates_at_gap(self, tmp_path):
        s = self._store(tmp_path)
        s.put_prefix(D1, page_payload(), persist=False)  # host only
        s.persist.store(D2, page_payload())  # persist only
        assert s.longest_prefix_run([D1, D2, D3]) == [
            (D1, "host"), (D2, "persist")]
        # a gap truncates the run even when later digests are resident
        assert s.longest_prefix_run([D3, D1]) == []
        assert s.stats.hits == 1 and s.stats.misses == 1

    def test_get_prefix_prefers_tier_over_persist(self, tmp_path):
        s = self._store(tmp_path)
        s.put_prefix(D1, page_payload(5.0), persist=True)
        payload, tier = s.get_prefix(D1)
        assert tier == "host"
        assert payload["kv"][0, 0, 0, 0, 0, 0] == 5.0
        # still resident after the read (prefix reads never consume)
        assert s.prefix_tier_of(D1) == "host"

    def test_needs_persist_is_persist_layer_only(self, tmp_path):
        s = self._store(tmp_path)
        s.put_prefix(D1, page_payload(), persist=True)
        s.put_prefix(D2, page_payload(), persist=False)
        assert s.needs_persist([D1, D2, D3]) == [D2, D3]
        no_persist = self._store(tmp_path, persist=False)
        assert no_persist.needs_persist([D1, D2]) == []

    def test_spill_contract_unchanged(self, tmp_path):
        s = self._store(tmp_path)
        assert s.put("req-1", page_payload(7.0))
        assert s.would_fit(64)
        got = s.get("req-1")
        assert got["kv"][0, 0, 0, 0, 0, 0] == 7.0
        assert s.get("req-1") is None  # consumed


class TestPrefixCacheAdopt:
    def _cache(self, enabled=True):
        from kserve_tpu.engine.kvcache import PageAllocator
        from kserve_tpu.engine.prefix_cache import PrefixCache

        alloc = PageAllocator(16)
        return PrefixCache(8, enabled, alloc), alloc

    def test_adopt_owns_ref_and_dedupes(self):
        cache, alloc = self._cache()
        pages = alloc.allocate(2)
        cache.adopt([(D1, pages[0]), (D2, pages[1])])
        assert cache.contains_key(D1) and cache.contains_key(D2)
        # a duplicate adoption frees the duplicate page back
        free_before = alloc.free_pages
        dup = alloc.allocate(1)
        cache.adopt([(D1, dup[0])])
        assert alloc.free_pages == free_before
        # adopted pages count as adopted hits on lookup via eviction seam:
        # (lookup needs a real digest chain; covered by the engine tests)
        assert cache.adopted == {D1, D2}

    def test_adopt_disabled_cache_frees_everything(self):
        cache, alloc = self._cache(enabled=False)
        before = alloc.free_pages
        pages = alloc.allocate(2)
        cache.adopt([(D1, pages[0]), (D2, pages[1])])
        assert alloc.free_pages == before


PREFIX = list(range(3, 35))  # 32 tokens = 4 full pages of 8


class TestEngineDemotionAndPageIn:
    @async_test
    async def test_evicted_prefix_pages_demote_then_page_back_in(
            self, tmp_path):
        """The full HBM round trip inside one engine life: cache pressure
        evicts cold prefix pages -> they demote into the host tier instead
        of dropping -> a later request with the same prefix pages them
        back in and serves them as hits."""
        engine = make_engine(
            num_pages=12, kv_offload="host", kv_offload_gib=1.0,
            kv_offload_dir=str(tmp_path),
        )
        params = SamplingParams(max_tokens=3, temperature=0.0,
                                ignore_eos=True)
        await engine.start()
        try:
            baseline = [
                o.token_id
                for o in await collect(engine, PREFIX + [100, 101], params)
            ]
            # different prompts force ensure_allocatable to evict PREFIX's
            # cached pages (11 usable pages cannot hold two 4-page
            # prefixes plus an active request)
            await collect(engine, [60 + i for i in range(32)] + [1, 2], params)
            await collect(engine, [110 + i for i in range(32)] + [3, 4], params)
            stats = engine.scheduler_state()["prefix_store"]
            assert stats["demotions"] > 0, stats
            assert stats["resident_digests"] > 0
            # the original prefix returns: paged in from the host tier,
            # token-for-token identical
            again = [
                o.token_id
                for o in await collect(engine, PREFIX + [100, 101], params)
            ]
            stats = engine.scheduler_state()["prefix_store"]
            assert stats["pageins"] > 0, stats
            assert stats["adopted_hit_tokens"] > 0, stats
            assert again == baseline
        finally:
            await engine.stop()

    @async_test
    async def test_hot_wake_restart_serves_prefix_from_persist(
            self, tmp_path):
        """The ISSUE 13 acceptance shape on a real CPU engine: reuse
        persists the shared prefix, a RESTARTED engine (same persist dir,
        same weights) pages it in from disk and serves prefix hits from
        request one — before any same-life prefill registered them."""
        params = SamplingParams(max_tokens=5, temperature=0.0,
                                ignore_eos=True)
        e1 = make_engine(kv_persist_dir=str(tmp_path))
        await e1.start()
        baseline = [
            o.token_id for o in await collect(e1, PREFIX + [100, 101], params)
        ]
        # reuse triggers the persist write-through
        await collect(e1, PREFIX + [110, 111], params)
        await wait_until(lambda: e1.scheduler_state()[
            "prefix_store"]["persist_digests"] >= 4)
        st1 = e1.scheduler_state()["prefix_store"]
        weights = e1.params
        await e1.stop()
        assert st1["persist_digests"] >= 4, st1

        e2 = make_engine(kv_persist_dir=str(tmp_path))
        e2.params = weights  # identical weights, as on a real node
        await e2.start()
        try:
            again = [
                o.token_id
                for o in await collect(e2, PREFIX + [100, 101], params)
            ]
            st2 = e2.scheduler_state()["prefix_store"]
            assert st2["pageins"] >= 4, st2
            assert st2["pagein_tokens_by_tier"].get("persist", 0) > 0, st2
            assert st2["adopted_hit_tokens"] > 0, st2
            assert again == baseline
        finally:
            await e2.stop()

    @async_test
    async def test_resume_consults_store_before_reprefilling(self, tmp_path):
        """GenerationCheckpoint resume rides the page-in path: a resumed
        stream on a fresh engine with the persisted prefix continues
        token-exactly AND pages the prompt prefix in instead of
        re-prefilling it — item 2's near-free migration, first leg."""
        from kserve_tpu.lifecycle.checkpoint import GenerationPreempted

        params = SamplingParams(max_tokens=16, temperature=0.0,
                                ignore_eos=True)
        e1 = make_engine(kv_persist_dir=str(tmp_path))
        await e1.start()
        baseline = [
            o.token_id for o in await collect(e1, PREFIX + [100, 101], params)
        ]
        await collect(e1, PREFIX + [110, 111], params)  # persist the prefix
        await wait_until(lambda: e1.scheduler_state()[
            "prefix_store"]["persist_digests"] >= 4)
        # a third stream checkpoints mid-generation
        gen = e1.generate(PREFIX + [100, 101], params)
        got = []
        async for out in gen:
            got.append(out.token_id)
            if len(got) >= 4:
                break
        ckpts = await e1.drain(deadline=Deadline.after(0.0, MONOTONIC))
        assert len(ckpts) == 1
        weights = e1.params
        await e1.stop()

        e2 = make_engine(kv_persist_dir=str(tmp_path))
        e2.params = weights
        await e2.start()
        try:
            resumed = [
                o.token_id
                async for o in e2.resume_generation(ckpts[0])
            ]
            st2 = e2.scheduler_state()["prefix_store"]
            assert st2["pageins"] > 0, st2
            salvaged = list(ckpts[0].generated)
            assert salvaged + resumed == baseline, (
                "resume must splice token-exactly through the store")
        finally:
            await e2.stop()

    @async_test
    async def test_corrupt_persist_entry_reprefills_never_crashes(
            self, tmp_path):
        params = SamplingParams(max_tokens=4, temperature=0.0,
                                ignore_eos=True)
        e1 = make_engine(kv_persist_dir=str(tmp_path))
        await e1.start()
        baseline = [
            o.token_id for o in await collect(e1, PREFIX + [100, 101], params)
        ]
        await collect(e1, PREFIX + [110, 111], params)
        await wait_until(lambda: e1.scheduler_state()[
            "prefix_store"]["persist_digests"] >= 4)
        weights = e1.params
        await e1.stop()
        entries = [n for n in os.listdir(str(tmp_path))
                   if n.endswith(".kvpage")]
        assert entries
        for name in entries:
            # tiny test-fixture write; nothing else runs on this loop
            path = os.path.join(str(tmp_path), name)
            with open(path, "wb") as f:  # jaxlint: disable=blocking-async
                f.write(b"bit rot")

        e2 = make_engine(kv_persist_dir=str(tmp_path))
        e2.params = weights
        await e2.start()
        try:
            again = [
                o.token_id
                for o in await collect(e2, PREFIX + [100, 101], params)
            ]
            st2 = e2.scheduler_state()["prefix_store"]
            assert st2["corrupt"] > 0, st2
            assert st2["pageins"] == 0, st2
            assert again == baseline, "re-prefill must stay token-exact"
        finally:
            await e2.stop()
        # the corrupt entry that was READ got unlinked (the run truncates
        # at the first bad page, so later entries may sit untouched)
        remaining = [n for n in os.listdir(str(tmp_path))
                     if n.endswith(".kvpage")]
        assert len(remaining) < len(entries)


class TestPrefixStoreStatsFlow:
    def test_scheduler_state_exports_block_only_with_store(self, tmp_path):
        engine = make_engine(kv_persist_dir=str(tmp_path))
        state = engine.scheduler_state()
        assert "prefix_store" in state
        for key in ("resident_digests", "hits", "misses", "demotions",
                    "pageins", "adopted_hit_tokens", "persist_digests"):
            assert key in state["prefix_store"]
        plain = make_engine()
        assert "prefix_store" not in plain.scheduler_state()

    def test_picker_carries_prefix_store_flat_and_nested(self):
        from kserve_tpu.scheduler.picker import EndpointPicker

        picker = EndpointPicker(["http://r0:8080"], poll_interval_s=999)
        block = {"resident_digests": 3, "pageins": 2, "hits": 5,
                 "pagein_tokens_by_tier": {"persist": 32}}
        picker.observe_state("http://r0:8080", {
            "queue_depth": 1, "prefix_store": block,
        })
        snap = picker.snapshot()[0]
        assert snap["prefix_store"]["resident_digests"] == 3
        # nested multi-model form: counts sum, tier dicts merge
        picker.observe_state("http://r0:8080", {
            "models": {
                "a": {"page_size": 8, "prefix_digests": [],
                      "prefix_store": {"pageins": 1, "hits": 2,
                                       "pagein_tokens_by_tier":
                                           {"persist": 16}}},
                "b": {"page_size": 8, "prefix_digests": [],
                      "prefix_store": {"pageins": 4, "hits": 1,
                                       "pagein_tokens_by_tier":
                                           {"host": 8}}},
            },
        })
        snap = picker.snapshot()[0]
        assert snap["prefix_store"]["pageins"] == 5
        assert snap["prefix_store"]["pagein_tokens_by_tier"] == {
            "persist": 16, "host": 8}

    def test_fleet_signals_carry_prefix_store(self):
        from kserve_tpu.autoscale.signals import FleetSignals

        fleet = FleetSignals.from_replica_states(
            [{"url": "http://r0:8080", "queue_depth": 0,
              "prefix_store": {"resident_digests": 7, "pageins": 1}}],
            at_s=10.0,
        )
        assert fleet.replicas[0].prefix_store["resident_digests"] == 7
        # wire round trip (EPP /state fleet block -> autoscaler CLI)
        rebuilt = FleetSignals.from_dict(fleet.to_dict())
        assert rebuilt.replicas[0].prefix_store["resident_digests"] == 7


# --------------------------------------------------------------------------
# Cross-replica page fabric (kvstore/peer.py, docs/kv_hierarchy.md
# "Cross-replica page serving")


import io

import httpx

from kserve_tpu.kvstore import (
    PAGE_ROUTE,
    PageVerifyError,
    PeerPageClient,
    PeerPageIndex,
    decode_page,
    decode_payload,
    digest_set_wire,
    encode_page,
)
from kserve_tpu.kvstore.persist import PERSIST_FORMAT
from kserve_tpu.resilience import BreakerConfig, BreakerRegistry, RetryPolicy


def npz_bytes(fill=1.0):
    """Raw persist-entry file bytes (what the page server wraps)."""
    buf = io.BytesIO()
    np.savez(buf, fmt=PERSIST_FORMAT, **page_payload(fill))
    return buf.getvalue()


class TestPeerWireCodec:
    """Tamper property tests: every mutation class a wire page can
    suffer — header flip, payload flip, trailing truncation, and a real
    page served under another page's key — is rejected at verification,
    BEFORE anything reaches the prefix cache."""

    def test_round_trip(self):
        raw = npz_bytes(3.0)
        wire = encode_page(D1, raw)
        assert decode_page(wire, D1) == raw
        got = decode_payload(raw)
        np.testing.assert_array_equal(got["kv"], page_payload(3.0)["kv"])

    def test_header_flips_rejected(self):
        wire = encode_page(D1, npz_bytes())
        # magic, version, embedded digest, length field — one flipped
        # bit anywhere in the header kills the page
        for off in (0, 3, 4, 5, 6, 13, 21, 24, 29):
            tampered = bytearray(wire)
            tampered[off] ^= 0xFF
            with pytest.raises(PageVerifyError):
                decode_page(bytes(tampered), D1)

    def test_payload_flips_rejected(self):
        raw = npz_bytes()
        wire = encode_page(D1, raw)
        start = len(wire) - 16 - len(raw)
        for off in range(start, len(wire) - 16, max(1, len(raw) // 9)):
            tampered = bytearray(wire)
            tampered[off] ^= 0x01
            with pytest.raises(PageVerifyError):
                decode_page(bytes(tampered), D1)

    def test_trailer_flip_rejected(self):
        tampered = bytearray(encode_page(D1, npz_bytes()))
        tampered[-1] ^= 0x80
        with pytest.raises(PageVerifyError):
            decode_page(bytes(tampered), D1)

    def test_truncation_rejected(self):
        wire = encode_page(D1, npz_bytes())
        for cut in (1, 7, 16, len(wire) // 2, len(wire) - 1):
            with pytest.raises(PageVerifyError):
                decode_page(wire[: len(wire) - cut], D1)

    def test_key_swap_between_real_pages_rejected(self):
        """Two HONEST pages served under each other's digests: both
        payloads verify byte-for-byte against their own key, neither may
        verify against the other's — integrity binds key to bytes."""
        w1 = encode_page(D1, npz_bytes(1.0))
        w2 = encode_page(D2, npz_bytes(2.0))
        assert decode_page(w1, D1) and decode_page(w2, D2)
        with pytest.raises(PageVerifyError):
            decode_page(w1, D2)
        with pytest.raises(PageVerifyError):
            decode_page(w2, D1)

    def test_rotten_payload_is_verify_error(self):
        # checksum-valid wire around bytes that were never a persist
        # entry: still a PageVerifyError, never an adoption
        wire = encode_page(D1, b"not an npz at all")
        with pytest.raises(PageVerifyError):
            decode_payload(decode_page(wire, D1))


class TestPeerPageIndex:
    def test_generation_aging_and_candidate_order(self):
        idx = PeerPageIndex()
        assert idx.update("http://b:1", digest_set_wire(1, [D1]))
        assert idx.update("http://a:1", digest_set_wire(2, [D2, D1]))
        # candidates are deterministically ordered (sorted by url)
        assert idx.peers_for(D1) == ["http://a:1", "http://b:1"]
        assert idx.peers_for(D2) == ["http://a:1"]
        # stale gossip (lower generation) is ignored...
        assert not idx.update("http://a:1", digest_set_wire(1, [D3]))
        assert idx.peers_for(D2) == ["http://a:1"]
        # ...a newer set replaces the old one wholesale
        assert idx.update("http://a:1", digest_set_wire(3, [D3]))
        assert idx.peers_for(D2) == []
        assert idx.peers_for(D3) == ["http://a:1"]
        assert idx.has(D1) and not idx.has(D2)
        idx.forget("http://a:1")
        assert idx.peers_for(D3) == []

    def test_unparseable_wire_ignored(self):
        idx = PeerPageIndex()
        assert not idx.update("http://a:1", None)
        assert not idx.update("http://a:1", "gibberish")
        assert not idx.update(
            "http://a:1", {"generation": "x", "digests": ["zz"]})
        assert idx.peers_for(D1) == []

    def test_wire_cap_marks_truncation(self):
        digests = [bytes([i]) * 16 for i in range(10)]
        wire = digest_set_wire(5, digests, cap=4)
        assert len(wire["digests"]) == 4
        assert wire["truncated"] is True
        full = digest_set_wire(5, digests)
        assert full["truncated"] is False
        assert full["digests"] == sorted(full["digests"])


PEER_A = "http://peer-a:8080"
PEER_B = "http://peer-b:8080"


def make_peer_client(handler, clock, digests=(D1,), peers=(PEER_A,), **kw):
    """A PeerPageClient over httpx.MockTransport + FakeClock: the same
    wiring the fleet sim uses, minus the fault plan."""
    index = PeerPageIndex()
    for url in peers:
        index.update(url, digest_set_wire(1, list(digests)))
    return PeerPageClient(
        httpx.AsyncClient(transport=httpx.MockTransport(handler)),
        index=index,
        retry=RetryPolicy(max_attempts=3, base_backoff_s=0.01,
                          max_backoff_s=0.05, retry_budget_s=5.0, seed=1),
        breakers=BreakerRegistry(
            BreakerConfig(window=4, failure_threshold=0.5, min_volume=1,
                          open_for_s=10.0),
            clock=clock),
        clock=clock, **kw)


class TestPeerPageClient:
    @async_test
    async def test_verified_hit_adopts_payload(self):
        clock = FakeClock()
        calls = []

        def handler(request):
            calls.append(str(request.url))
            return httpx.Response(200, content=encode_page(D1, npz_bytes(7.0)))

        client = make_peer_client(handler, clock)
        payload = await client.fetch_page(D1)
        assert payload is not None
        np.testing.assert_array_equal(
            payload["kv"], page_payload(7.0)["kv"])
        assert client.stats["hit"] == 1
        assert calls == [f"{PEER_A}{PAGE_ROUTE}/{D1.hex()}"]
        await client.client.aclose()

    @async_test
    async def test_404_is_clean_miss_not_failure(self):
        clock = FakeClock()

        def handler(request):
            return httpx.Response(404, json={"error": "page not resident"})

        client = make_peer_client(handler, clock)
        assert await client.fetch_page(D1) is None
        assert client.stats["miss"] == 1
        # a stale index is not peer sickness: the breaker stays closed
        assert client.breakers.allow(PEER_A)
        await client.client.aclose()

    @async_test
    async def test_corrupt_page_counted_never_retried_never_adopted(self):
        clock = FakeClock()
        noted, calls = [], []

        def handler(request):
            calls.append(1)
            body = bytearray(encode_page(D1, npz_bytes()))
            body[len(body) // 2] ^= 0xFF  # the lying 200
            return httpx.Response(200, content=bytes(body))

        client = make_peer_client(handler, clock, on_bad_page=noted.append)
        assert await client.fetch_page(D1) is None
        assert len(calls) == 1, "a peer that served garbage must NOT be retried"
        assert client.stats["corrupt"] == 1
        assert client.bad_pages == {PEER_A: 1}
        assert noted == [PEER_A]
        await client.client.aclose()

    @async_test
    async def test_partition_retries_then_breaker_opens_then_recovers(self):
        clock = FakeClock()
        calls = []
        healthy = False

        def handler(request):
            calls.append(1)
            if not healthy:
                raise httpx.ConnectError("refused", request=request)
            return httpx.Response(200, content=encode_page(D1, npz_bytes()))

        client = make_peer_client(handler, clock)
        assert await client.fetch_page(D1) is None
        assert len(calls) == 3, "partition must burn the retry budget"
        assert client.stats["timeout"] == 1
        # the breaker is now open: the next fetch skips the peer with
        # ZERO network attempts (local-only degradation)
        assert await client.fetch_page(D1) is None
        assert len(calls) == 3
        assert client.stats["breaker_open"] == 1
        # cooldown passes, the peer heals: the half-open probe converges
        # straight back to verified hits
        clock.advance(11.0)
        healthy = True
        assert await client.fetch_page(D1) is not None
        assert client.stats["hit"] == 1
        await client.client.aclose()

    @async_test
    async def test_slow_response_past_deadline_reads_as_miss(self):
        clock = FakeClock()

        def handler(request):
            clock.advance(3.0)  # straggler: past the 2 s fetch deadline
            return httpx.Response(200, content=encode_page(D1, npz_bytes()))

        client = make_peer_client(handler, clock)
        assert await client.fetch_page(D1) is None, (
            "a late page — even a verifiable one — must not hold the "
            "admission back")
        assert client.stats["timeout"] == 1
        await client.client.aclose()

    @async_test
    async def test_fetch_page_fails_over_past_the_lying_peer(self):
        clock = FakeClock()

        def handler(request):
            body = bytearray(encode_page(D1, npz_bytes(4.0)))
            if request.url.host == "peer-a":
                body[len(body) // 2] ^= 0xFF
            return httpx.Response(200, content=bytes(body))

        client = make_peer_client(handler, clock, peers=(PEER_A, PEER_B))
        payload = await client.fetch_page(D1)
        assert payload is not None, "the honest second candidate serves"
        assert client.stats["corrupt"] == 1 and client.stats["hit"] == 1
        assert client.bad_pages == {PEER_A: 1}
        await client.client.aclose()

    @async_test
    async def test_self_url_excluded_from_candidates(self):
        clock = FakeClock()

        def handler(request):  # pragma: no cover - must never run
            raise AssertionError("self must not be fetched from")

        client = make_peer_client(handler, clock, self_url=PEER_A)
        assert await client.fetch_page(D1) is None
        assert all(v == 0 for v in client.stats.values())
        await client.client.aclose()
